"""Jit-ready wrappers around the Pallas kernels.

Public API (model-layout shapes, GQA folded into BlockSpec index maps):
  flash_attention(q, k, v, ...)     — (B, Sq, H, D) × (B, Sk, KH, D) → (B, Sq, H, D)
  decode_attention(q, k, v, valid)  — (B, 1|·, H, D) one-token vs cache
  ssm_scan(x, loga, b, c)           — (B, S, H, P) chunked SSD

flash_attention is differentiable: forward runs the kernel, backward falls
back to the jnp reference VJP under recompute (standard flash-training
pattern without a hand-written bwd kernel).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssm_scan import ssm_scan_fwd


def _fold_heads(q, k, v):
    """(B,S,H,D) → (B·H, S, D); (B,S,KH,D) → (B·KH, S, D)."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, k.shape[1], d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, v.shape[1], d)
    return qf, kf, vf


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    q_offset: int = 0,
    window: int = 0,
    softmax_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    return _flash_fwd_impl(
        q, k, v, causal, q_offset, window, softmax_scale, block_q, block_k, interpret
    )


def _flash_fwd_impl(q, k, v, causal, q_offset, window, softmax_scale, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    kh = k.shape[2]
    scale = softmax_scale if softmax_scale is not None else 1.0 / d**0.5
    qf, kf, vf = _fold_heads(q, k, v)
    out = flash_attention_fwd(
        qf, kf, vf,
        q_per_kv=h // kh, causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def _flash_fwd_rule(q, k, v, causal, q_offset, window, softmax_scale, block_q, block_k, interpret):
    out = _flash_fwd_impl(q, k, v, causal, q_offset, window, softmax_scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd_rule(causal, q_offset, window, softmax_scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: kref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, q_offset=q_offset,
            softmax_scale=softmax_scale,
        ),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def decode_attention(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (B, S, KH, D)
    v: jax.Array,
    valid: jax.Array,  # (B, S) bool
    softmax_scale: Optional[float] = None,
    block_k: int = 512,
    return_partials: bool = False,
    interpret: bool = False,
):
    b, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = softmax_scale if softmax_scale is not None else 1.0 / d**0.5
    qf = q.reshape(b, kh, g, d).reshape(b * kh, g, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, s, d)
    validf = jnp.repeat(valid.astype(jnp.int32), kh, axis=0).reshape(b * kh, 1, s)
    out, m, l = decode_attention_fwd(
        qf, kf, vf, validf, scale=scale, block_k=block_k,
        normalize=not return_partials, interpret=interpret,
    )
    out = out.reshape(b, kh, g, d).reshape(b, h, d)
    if return_partials:
        return out, m.reshape(b, h), l.reshape(b, h)
    return out.astype(q.dtype)


def combine_decode_partials(outs, ms, ls):
    """logsumexp-combine flash-decode partials from sequence shards.

    outs: list of (B, H, D) unnormalized; ms/ls: (B, H). Also usable inside
    shard_map via psum of the rescaled terms (parallel/flash_decode.py).
    """
    m_g = jnp.max(jnp.stack(ms), axis=0)
    num = 0.0
    den = 0.0
    for o, m, l in zip(outs, ms, ls):
        w = jnp.exp(m - m_g)
        num = num + o * w[..., None]
        den = den + l * w
    return num / jnp.maximum(den, 1e-30)[..., None]


def ssm_scan(
    x: jax.Array,  # (B, S, H, P)
    loga: jax.Array,  # (B, S, H)
    b: jax.Array,  # (B, S, H, N)
    c: jax.Array,  # (B, S, H, N)
    chunk: int = 256,
    interpret: bool = False,
):
    B, S, H, P = x.shape
    N = b.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    laf = loga.transpose(0, 2, 1).reshape(B * H, S)
    bf = b.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    cf = c.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    y, h = ssm_scan_fwd(xf, laf, bf, cf, chunk=chunk, interpret=interpret)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    h = h.reshape(B, H, N, P)
    return y, h
