"""Plain float32 reference of the served decoder, independent of the program.

The forward pass of each architecture (``hidden`` and ``head``) is in its
module under ``arch/``, built from the pieces here: the matrix product,
RMSNorm and the rotary embedding. Here are those pieces and the gaps read
through the head.

Every matrix product runs at ``Precision.HIGHEST`` in float32, on float32
copies of its operands, so weights made in bfloat16 are read at the values
the program was handed. The whole sequence goes through at once, one
sequence per call, and the head's logits are made in blocks of positions
so that the (positions × vocab) matrix never exists whole.

``fp8=True`` is the control: every matrix product's operands rounded
to float8 e4m3 after scaling by their largest magnitude (per tensor for
weights, per row for activations), the precision step below the bf16
compute the configuration states.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from chipbench import spec

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn
LOGIT_BLOCK = 256


def _q8(x: jax.Array, per_row: bool) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) if per_row else jnp.max(jnp.abs(x))
    scale = F8_MAX / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(subscripts: str, x, w, fp8: bool, w_is_weight: bool = True):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if fp8:
        x = _q8(x, per_row=True)
        w = _q8(w, per_row=not w_is_weight)
    return jnp.einsum(subscripts, x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    """x: (S, heads, hd); rotate-half rotary embedding at positions 0..S-1."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocks(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[0] // LOGIT_BLOCK, LOGIT_BLOCK, *x.shape[1:])


def _gaps(c: dict, w: dict, tokens, targets, control: bool):
    """Per position, the reference's best logit minus its logit of
    ``targets`` (the served token); with ``control``, also the reference's
    gap of the token the float8 pass ranks first."""
    arch = spec.arch(c)
    head = arch.head(c, w)
    h_ref = _blocks(arch.hidden(c, w, tokens))
    xs = (h_ref, _blocks(targets))
    if control:
        xs += (_blocks(arch.hidden(c, w, tokens, fp8=True)),)

    def block(args):
        logits = _mm("sd,dv->sv", args[0], head, False)
        best = logits.max(axis=-1)
        served = jnp.take_along_axis(logits, args[1][:, None], axis=-1)[:, 0]
        if not control:
            return best - served
        low = _mm("sd,dv->sv", args[2], head, True).argmax(axis=-1)
        return best - served, best - jnp.take_along_axis(logits, low[:, None], axis=-1)[:, 0]

    out = jax.lax.map(block, xs)
    return jax.tree.map(lambda a: a.reshape(-1), out)


@functools.lru_cache(maxsize=None)
def gap_fn(config_json: str, control: bool):
    """The jitted gap program of one configuration (a JSON string, so the
    cache key is the file's content)."""
    c = json.loads(config_json)
    return jax.jit(lambda w, tokens, targets: _gaps(c, w, tokens, targets, control))
