"""Plain float32 reference of the served decoder, independent of the program.

A dense GQA decoder as the configuration file describes it (Qwen3 /
InternLM2 style): token embedding; per layer RMSNorm → q/k/v projections,
optional per-head RMSNorm of q and k (Qwen3's ``q_norm``/``k_norm``),
rotary embedding (rotate-half, ``rope_theta``), causal softmax attention
with ``num_attention_heads / num_key_value_heads`` query heads per key
head, output projection, residual; RMSNorm → SwiGLU FFN, residual; final
RMSNorm and the head (the embedding's transpose when tied). RMSNorm is
``x / sqrt(mean(x²) + eps) · (1 + g)``, the gain convention of the
weights in ``weights.py``.

Every matrix product runs at ``Precision.HIGHEST`` in float32. The whole
sequence goes through at once, one sequence per call, and the head's
logits are made in blocks of positions so that the (positions × vocab)
matrix never exists whole.

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

from chipbench.weights import dims

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn
LOGIT_BLOCK = 256


def _q8(x: jax.Array, per_row: bool) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) if per_row else jnp.max(jnp.abs(x))
    scale = F8_MAX / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(spec: str, x, w, fp8: bool, w_is_weight: bool = True):
    if fp8:
        x = _q8(x, per_row=True)
        w = _q8(w, per_row=not w_is_weight)
    return jnp.einsum(spec, x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (S, heads, hd); rotate-half rotary embedding at positions 0..S-1."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def hidden(c: dict, w: dict, tokens: jax.Array, fp8: bool = False) -> jax.Array:
    """Final-normed hidden states (S, d) of one sequence of token ids."""
    z = dims(c)
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    groups = z["H"] // z["KH"]
    s = tokens.shape[0]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def layer(h, p):
        a = p["attn"]
        x = _rms(h, p["norm"], eps)
        q = _mm("sd,dhk->shk", x, a["wq"], fp8)
        k = _mm("sd,dhk->shk", x, a["wk"], fp8)
        v = _mm("sd,dhk->shk", x, a["wv"], fp8)
        if c["qk_norm"]:
            q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, groups, axis=1)  # query head i reads key head i // groups
        v = jnp.repeat(v, groups, axis=1)
        scores = _mm("qhk,shk->hqs", q, k, fp8, w_is_weight=False) / jnp.sqrt(float(z["hd"]))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        out = _mm("hqs,shk->qhk", probs, v, fp8, w_is_weight=False)
        h = h + _mm("qhk,hkd->qd", out, a["wo"], fp8)
        f = p["ffn"]
        x = _rms(h, p["ffn_norm"], eps)
        gate = _mm("sd,df->sf", x, f["gate"], fp8)
        up = _mm("sd,df->sf", x, f["up"], fp8)
        return h + _mm("sf,fd->sd", jax.nn.silu(gate) * up, f["down"], fp8), None

    h = w["embed"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(layer, h, w["layers"]["b0"])
    return _rms(h, w["final_norm"], eps)


def _head(c: dict, w: dict) -> jax.Array:
    return w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]


def _blocks(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[0] // LOGIT_BLOCK, LOGIT_BLOCK, *x.shape[1:])


def _gaps(c: dict, w: dict, tokens, targets, control: bool):
    """Per position, the reference's best logit minus its logit of
    ``targets`` (the served token); with ``control``, also the reference's
    gap of the token the float8 pass ranks first."""
    head = _head(c, w)
    h_ref = _blocks(hidden(c, w, tokens))
    xs = (h_ref, _blocks(targets))
    if control:
        xs += (_blocks(hidden(c, w, tokens, fp8=True)),)

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
