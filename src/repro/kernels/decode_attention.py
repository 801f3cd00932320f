"""Flash-decode kernel: one query token against a (possibly huge) KV cache.

The sequence dimension of the cache is tiled into VMEM blocks and iterated
by the innermost grid dim with online-softmax scratch, so HBM traffic is one
streaming pass over K and V — the decode hot loop is bandwidth-bound, which
makes this the memory-roofline kernel of the framework.

Two modes:
  * normalized output (single-host attention);
  * ``return_partials``: emit (out_unnormalized, m, l) so the caller can
    logsumexp-combine partial results across sequence shards — the cross-chip
    flash-decode used when the cache is sharded over the ``model`` mesh axis
    (shard_map + psum combine in parallel/flash_decode.py).

The per-batch ``valid`` mask handles ring buffers (sliding-window caches)
and partially-filled caches without any host-side slicing.

Block layout: the TPU lowering requires the last two dimensions of every
block to be divisible by (8, 128) or equal to the array's. So the mask and
the ``m``/``l`` outputs carry a unit middle axis — ``(BKH, 1, S)`` and
``(BKH, 1, G)`` with ``(1, 1, ·)`` blocks — rather than ``(1, ·)`` blocks
over a 2-D ``(BKH, ·)`` array, which the chip's compiler refuses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    q_ref,  # (1, G, D)
    k_ref,  # (1, bk, D)
    v_ref,  # (1, bk, D)
    valid_ref,  # (1, 1, bk) int32 (bool as int)
    o_ref,  # (1, G, D)
    m_ref,  # (1, 1, G)
    l_ref,  # (1, 1, G)
    m_scr,  # (G,) f32
    l_scr,  # (G,) f32
    acc_scr,  # (G, D) f32
    *,
    scale: float,
    num_k_blocks: int,
    normalize: bool,
):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (G, D)
    k = k_ref[0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, bk)
    ok = valid_ref[0] > 0  # (1, bk)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # Masked probabilities must be written as zero, not left to exp
    # underflow: while m_new is still NEG_INF (no valid key seen yet) a
    # masked entry's exponent is NEG_INF - NEG_INF = 0, so exp() returns 1
    # and the block contributes phantom weight to l/acc. A later valid
    # block cancels it through corr = exp(NEG_INF - m) = 0, but a row whose
    # valid keys all live past the first blocks — or an all-invalid row,
    # or the zero-padded seq_len % block_k remainder of the last block —
    # leaks the phantom mass into l (and, unnormalized, into the partials
    # the cross-shard combine consumes).
    p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv
    m_scr[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        if normalize:
            denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
            o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        else:
            o_ref[0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0] = m_scr[...][None, :].astype(m_ref.dtype)
        l_ref[0] = l_scr[...][None, :].astype(l_ref.dtype)


def decode_attention_fwd(
    q: jax.Array,  # (BKH, G, D)   — q heads grouped per kv head
    k: jax.Array,  # (BKH, S, D)
    v: jax.Array,
    valid: jax.Array,  # (BKH, 1, S) int32
    *,
    scale: float,
    block_k: int = 512,
    normalize: bool = True,
    interpret: bool = False,
):
    bkh, g, d = q.shape
    s = k.shape[1]
    bk = min(block_k, s)
    nk = -(-s // bk)
    pad = nk * bk - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, 0), (0, pad)))

    kernel = functools.partial(
        _decode_kernel, scale=scale, num_k_blocks=nk, normalize=normalize
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid=(bkh, nk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, g, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, g), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, g), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkh, g, d), jnp.float32),
            jax.ShapeDtypeStruct((bkh, 1, g), jnp.float32),
            jax.ShapeDtypeStruct((bkh, 1, g), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(q, k, v, valid)
    return out, m, l
