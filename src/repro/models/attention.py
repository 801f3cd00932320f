"""Grouped-query attention with RoPE, qk-norm, sliding window, KV caching.

Implementations (``RunConfig.attention_impl``):

* ``xla``      — plain softmax(QKᵀ)V; materializes (Sq, Skv) scores in HBM.
* ``chunked``  — two-level ``lax.scan`` flash-style attention: running max /
                 normalizer over KV chunks, q processed in blocks. Never
                 materializes the full score matrix — this is the pure-JAX
                 twin of the Pallas kernel and the default for dry-runs.
* ``pallas`` / ``pallas_interpret`` — the Pallas TPU kernel
                 (`repro.kernels.flash_attention`), interpret mode on CPU.

Decode uses a ring-buffer KV cache (capacity = sliding window when set), with
the cache sequence dimension sharded over the ``model`` mesh axis so that
XLA's partial-softmax collectives implement cross-chip flash-decode (see
DESIGN.md §3). The decode step takes a per-slot *position vector*, so one
dispatch serves a continuous batch whose rows sit at different cache
positions. Its attention is chosen from the platform
(:func:`resolve_decode_impl`): on a TPU the Pallas flash-decode kernel
(`repro.kernels.decode_attention`) with the per-row ring/partial-fill
``valid`` mask, elsewhere the masked-softmax einsum, asserted bit-close to
the kernel's interpret mode in tests/test_consistency.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models.common import (
    ParamDef,
    apply_rope,
    causal_mask,
    norm_def,
    nrm,
    rms_norm,
)
from repro.parallel.sharding import ShardingRules, shard_constraint

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig) -> dict:
    hd = cfg.head_dim_
    defs = {
        "wq": ParamDef((cfg.d_model, cfg.num_heads, hd), ("fsdp", "tp", None), nrm()),
        "wk": ParamDef((cfg.d_model, cfg.num_kv_heads, hd), ("fsdp", "tp", None), nrm()),
        "wv": ParamDef((cfg.d_model, cfg.num_kv_heads, hd), ("fsdp", "tp", None), nrm()),
        "wo": ParamDef((cfg.num_heads, hd, cfg.d_model), ("tp", None, "fsdp"), nrm(fan_in_axis=2)),
    }
    if cfg.qk_norm:
        defs["q_norm"] = norm_def(hd)
        defs["k_norm"] = norm_def(hd)
    return defs


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _split_gqa(q: jax.Array, num_kv: int) -> jax.Array:
    """(B, S, H, D) -> (B, S, KH, G, D)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, d)


def _xla_attention(q, k, v, *, q_offset, window, scale, kv_valid=None):
    """Reference/naive path. q: (B,Sq,H,D); k,v: (B,Skv,KH,D)."""
    kh = k.shape[2]
    qg = _split_gqa(q, kh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores *= scale
    mask = causal_mask(q.shape[1], k.shape[1], q_offset, window)
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, :] if kv_valid.ndim == 2 else mask & kv_valid
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    else:
        scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(q.shape)


def _chunked_attention(q, k, v, *, q_offset, window, scale, q_chunk, kv_chunk, unroll=False):
    """Flash-style attention: scan q blocks × scan kv blocks, O(chunk²) memory."""
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh

    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)
    nq = -(-sq // qc)
    nk = -(-skv // kc)
    q_pad, k_pad = nq * qc - sq, nk * kc - skv
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))

    # (nq, B, qc, KH, G, D) / (nk, B, kc, KH, D)
    qb = q.reshape(b, nq, qc, kh, g, d).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(b, nk, kc, kh, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, kc, kh, d).transpose(1, 0, 2, 3, 4)

    qpos = (jnp.arange(nq * qc) + q_offset).reshape(nq, qc)
    kpos = jnp.arange(nk * kc).reshape(nk, kc)
    kvalid = (jnp.arange(nk * kc) < skv).reshape(nk, kc)

    def q_block(_, inputs):
        qi, qp = inputs  # (B,qc,KH,G,D), (qc,)

        def kv_block(carry, kv_in):
            m, l, acc = carry
            ki, vi, kp, kval = kv_in
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi.astype(jnp.float32), ki.astype(jnp.float32)
            ) * scale
            mask = kp[None, :] <= qp[:, None]
            if window:
                mask &= kp[None, :] > qp[:, None] - window
            mask &= kval[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (m_new, l, acc), None

        m0 = jnp.full((b, kh, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kh, g, qc, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), (kb, vb, kpos, kvalid), unroll=unroll)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 3, 1, 2, 4)  # (B,qc,KH,G,D)

    _, out = jax.lax.scan(q_block, None, (qb, qpos), unroll=unroll)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * qc, h, d)
    return out[:, :sq].astype(q.dtype)


def _pallas_attention(q, k, v, *, q_offset, window, scale, interpret):
    from repro.kernels import ops as kops

    return kops.flash_attention(
        q, k, v, causal=True, q_offset=q_offset, window=window,
        softmax_scale=scale, interpret=interpret,
    )


def _pad_heads(q, k, v, multiple: int):
    """Pad head counts to a multiple (zero fake heads) so indivisible head
    counts still shard over the model axis. Function-preserving: padded q
    heads attend to zero-k/v fake kv heads (MHA) or ride as extra GQA groups;
    their outputs are sliced away by the caller. Returns (q', k', v', H)."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    if h % multiple == 0:
        return q, k, v, h
    if g == 1:  # MHA: pad q and kv head dims together
        h_pad = -(-h // multiple) * multiple
        pad = ((0, 0), (0, 0), (0, h_pad - h), (0, 0))
        return jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), h
    # GQA: grow the per-kv group count until flat heads divide the axis
    g_pad = g
    while (kh * g_pad) % multiple:
        g_pad += 1
    qg = q.reshape(b, sq, kh, g, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    return qg.reshape(b, sq, kh * g_pad, d), k, v, h


def _unpad_heads(out, h_orig, kh_orig):
    b, sq, h_pad, d = out.shape
    if h_pad == h_orig:
        return out
    g = h_orig // kh_orig
    if g == 1:  # MHA path: flat head slice
        return out[:, :, :h_orig]
    g_pad = h_pad // kh_orig
    return out.reshape(b, sq, kh_orig, g_pad, d)[:, :, :, :g].reshape(b, sq, h_orig, d)


def multihead_attention(run: RunConfig, q, k, v, *, q_offset=0, window=0, rules=None):
    """Dispatch on the configured implementation. Shapes as in _xla_attention."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    kh_orig = k.shape[2]
    h_orig = q.shape[2]
    if run.pad_attention_heads_to:
        q, k, v, h_orig = _pad_heads(q, k, v, run.pad_attention_heads_to)
        # the whole point of padding: the padded head dim now divides the
        # model axis, so re-constrain here (the pre-padding constraint in
        # _project_qkv was dropped as indivisible)
        q = shard_constraint(q, rules, ("batch", None, "tp", None))
        k = shard_constraint(k, rules, ("batch", None, "tp", None))
        v = shard_constraint(v, rules, ("batch", None, "tp", None))
    impl = run.attention_impl
    if impl == "xla":
        out = _xla_attention(q, k, v, q_offset=q_offset, window=window, scale=scale)
    elif impl == "chunked":
        out = _chunked_attention(
            q, k, v, q_offset=q_offset, window=window, scale=scale,
            q_chunk=run.attention_chunk, kv_chunk=run.attention_chunk,
            unroll=run.scan_unroll,
        )
    elif impl in ("pallas", "pallas_interpret"):
        out = _pallas_attention(
            q, k, v, q_offset=q_offset, window=window, scale=scale,
            interpret=(impl == "pallas_interpret"),
        )
    else:
        raise ValueError(f"unknown attention_impl {impl!r}")
    if run.pad_attention_heads_to and out.shape[2] != h_orig:
        out = _unpad_heads(out, h_orig, kh_orig)
    return out


# ---------------------------------------------------------------------------
# Block-level apply (projections + rope + attention [+ cache])
# ---------------------------------------------------------------------------


def _project_qkv(cfg: ModelConfig, params, x, positions, rules):
    dt = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_constraint(q, rules, ("batch", None, "tp", None))
    k = shard_constraint(k, rules, ("batch", None, "tp", None))
    v = shard_constraint(v, rules, ("batch", None, "tp", None))
    return q, k, v


def attn_apply_full(
    cfg: ModelConfig,
    run: RunConfig,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    rules: Optional[ShardingRules],
    return_kv: bool = False,
):
    """Training / prefill attention over the full sequence.

    x: (B, S, D) post-norm residual input; positions: (S,) or (B, S).
    """
    dt = jnp.dtype(cfg.compute_dtype)
    q, k, v = _project_qkv(cfg, params, x, positions, rules)
    out = multihead_attention(run, q, k, v, q_offset=0, window=cfg.sliding_window, rules=rules)
    out = shard_constraint(out, rules, ("batch", None, "tp", None))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    if return_kv:
        return y, (k, v)
    return y


def cache_capacity(cfg: ModelConfig, max_len: int) -> int:
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def attn_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    cap = cache_capacity(cfg, max_len)
    shape = (batch, cap, cfg.num_kv_heads, cfg.head_dim_)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def attn_cache_axes() -> dict:
    # Cache sequence dim sharded over `model` → XLA emits cross-chip
    # flash-decode (partial softmax + all-reduce) automatically.
    return {
        "k": ("batch", "kv_seq", None, None),
        "v": ("batch", "kv_seq", None, None),
    }


def attn_fill_cache(cfg: ModelConfig, cache: dict, k: jax.Array, v: jax.Array) -> dict:
    """Write prefill K/V (B, S, KH, D) into a fresh cache (ring-aware)."""
    cap = cache["k"].shape[1]
    s = k.shape[1]
    if s >= cap:  # keep the trailing window, ring-ordered
        tail_k, tail_v = k[:, s - cap:], v[:, s - cap:]
        # position p lands in slot p % cap
        slots = jnp.arange(s - cap, s) % cap
        order = jnp.argsort(slots)
        return {"k": tail_k[:, order], "v": tail_v[:, order]}
    return {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1),
    }


def resolve_decode_impl(impl: str) -> str:
    """The decode-attention implementation for the platform this traces on.

    ``auto`` is the Pallas kernel on a TPU and the einsum elsewhere (the
    kernel has no compiled CPU lowering). An explicit name is honoured, so
    tests and compile rehearsals can pin one path; interpret mode is for
    CPU tests and is refused on a TPU, where it would silently run the
    kernel as slow emulated XLA."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        return "kernel" if on_tpu else "einsum"
    if impl == "kernel_interpret" and on_tpu:
        raise ValueError("kernel_interpret is for CPU tests; a TPU runs the compiled kernel")
    return impl


def attn_apply_step(
    cfg: ModelConfig,
    run: RunConfig,
    params: dict,
    cache: dict,
    x: jax.Array,
    pos: jax.Array,
    rules: Optional[ShardingRules],
):
    """Single-token decode. x: (B, 1, D); pos: (B,) int32 — tokens so far
    *per slot*, so one dispatch serves a batch whose rows sit at different
    cache positions (the continuous-batching contract; a scalar pos
    broadcasts for the uniform case)."""
    dt = jnp.dtype(cfg.compute_dtype)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
    positions = pos[:, None]  # (B, 1) — per-row RoPE phase
    q, k, v = _project_qkv(cfg, params, x, positions, rules)

    cap = cache["k"].shape[1]
    slot = pos % cap if cfg.sliding_window else jnp.minimum(pos, cap - 1)
    # Elementwise masked write (iota == slot): shards cleanly along the
    # seq-sharded cache dim. A dynamic_update_slice here makes GSPMD reshard
    # the entire cache (head-layout ⇄ seq-layout all-to-alls, ~cache-size
    # bytes per layer per token); the select keeps every shard local.
    k = shard_constraint(k, rules, ("batch", None, None, None))
    v = shard_constraint(v, rules, ("batch", None, None, None))
    idx = jnp.arange(cap)
    write = idx[None, :, None, None] == slot[:, None, None, None]
    new_k = jnp.where(write, k.astype(cache["k"].dtype), cache["k"])
    new_v = jnp.where(write, v.astype(cache["v"].dtype), cache["v"])
    new_k = shard_constraint(new_k, rules, attn_cache_axes()["k"])
    new_v = shard_constraint(new_v, rules, attn_cache_axes()["v"])

    # validity, per row: slots < pos+1 filled (full cache: monotone; ring:
    # all once wrapped) — (B, cap), exactly the mask shape the flash-decode
    # kernel consumes for ring/partially-filled caches
    if cfg.sliding_window:
        valid = (idx[None, :] <= slot[:, None]) | (pos[:, None] >= cap)
    else:
        valid = idx[None, :] <= slot[:, None]

    scale = 1.0 / cfg.head_dim_**0.5
    impl = resolve_decode_impl(run.decode_attention_impl)
    if impl in ("kernel", "kernel_interpret"):
        from repro.kernels import ops as kops

        out = kops.decode_attention(
            q[:, 0], new_k, new_v, valid, softmax_scale=scale,
            interpret=(impl == "kernel_interpret"),
        )[:, None]  # (B, H, D) -> (B, 1, H, D)
        out = out.astype(dt)
    elif impl == "einsum":
        kh = cfg.num_kv_heads
        qg = _split_gqa(q, kh)  # (B,1,KH,G,D)
        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), new_k.astype(jnp.float32)
        ) * scale
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, new_v.astype(jnp.float32))
        out = out.reshape(q.shape).astype(dt)
    else:
        raise ValueError(f"unknown decode_attention_impl {impl!r}")
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return y, {"k": new_k, "v": new_v}
