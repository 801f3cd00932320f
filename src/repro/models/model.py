"""Model assembly: embedding → lax.scan over block periods → LM head.

The layer stack is expressed as ``lax.scan`` over *periods* (the repeating
block pattern — length 1 for dense models, 8 for jamba/xLSTM), so compiled
HLO size is depth-independent: llama3-405b's 126 layers compile as one body.
Heterogeneous block kinds (attn / mamba / mlstm / slstm) and MoE-vs-dense FFN
placement are resolved *inside* the period at trace time, which keeps every
assigned architecture on this single code path.

Three entry points mirror the workload kinds:
  forward()      — training forward (logits + aux metrics)
  prefill()      — forward + KV/state cache construction (inference-prefill)
  decode_step()  — one token with cache (inference-decode / long-context)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm
from repro.models.common import (
    ParamDef,
    build_params,
    build_shapes,
    build_specs,
    is_def,
    mlp_apply,
    mlp_defs,
    norm_def,
    nrm,
    param_count,
    rms_norm,
    softcap,
    stack_defs,
    trunc_nrm,
)
from repro.parallel.sharding import ShardingRules, shard_constraint

FRONTEND_FEATURE_DIM = {"audio_frames": 128, "vision_patches": 1152}
DEFAULT_PREFIX_LEN = 256


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def _block_defs(cfg: ModelConfig, j: int) -> dict:
    kind = cfg.layer_kind(j)
    d: dict[str, Any] = {}
    if kind == "attn":
        d["norm"] = norm_def(cfg.d_model)
        d["attn"] = attn.attn_defs(cfg)
    elif kind == "mamba":
        d["norm"] = norm_def(cfg.d_model)
        d["mamba"] = ssm.mamba_defs(cfg)
    elif kind == "mlstm":
        d["mlstm"] = ssm.mlstm_defs(cfg)
    elif kind == "slstm":
        d["slstm"] = ssm.slstm_defs(cfg)
    else:
        raise ValueError(kind)
    if cfg.layer_is_moe(j):
        d["ffn_norm"] = norm_def(cfg.d_model)
        d["moe"] = moe_lib.moe_defs(cfg)
    elif cfg.d_ff and kind in ("attn", "mamba"):
        d["ffn_norm"] = norm_def(cfg.d_model)
        d["ffn"] = mlp_defs(cfg.d_model, cfg.d_ff)
    return d


def model_defs(cfg: ModelConfig) -> dict:
    p = cfg.period
    layer_defs = {f"b{j}": _block_defs(cfg, j) for j in range(p)}
    defs: dict[str, Any] = {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("tp", "fsdp"), trunc_nrm(0.02)),
        "layers": stack_defs(layer_defs, cfg.num_periods),
        "final_norm": norm_def(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size), ("fsdp", "tp"), nrm())
    if cfg.frontend:
        feat = FRONTEND_FEATURE_DIM[cfg.frontend]
        defs["frontend"] = {"proj": ParamDef((feat, cfg.d_model), (None, "fsdp"), nrm())}
    return defs


def serving_params(cfg: ModelConfig, params: Optional[dict]) -> Optional[dict]:
    """The tree the inference step programs read: every matrix leaf cast
    once to ``cfg.compute_dtype``, every other leaf as it was.

    A matrix is a leaf of rank ≥ 2 once the stacked period axis of
    ``layers`` is set aside. The model rounds each matrix to the compute
    dtype where it reads it, so a jitted step handed float32 matrices
    converts all of them again in every call; handed this tree, its
    matmuls read the same bits and the converts drop out. The vectors
    keep their dtype: the norm gains, qk-norm and the SSM's
    ``a_log``/``dt_bias`` are read in float32, and the rest (``d_skip``,
    gate biases) are a few hundred bytes a layer. ``None`` passes through.
    """
    if params is None:
        return None
    dt = jnp.dtype(cfg.compute_dtype)

    def cast(tree, stacked: int):
        return jax.tree.map(lambda x: x.astype(dt) if x.ndim - stacked >= 2 else x, tree)

    return {k: cast(v, int(k == "layers")) for k, v in params.items()}


def init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    return build_params(model_defs(cfg), key)


def model_specs(cfg: ModelConfig, rules: Optional[ShardingRules]):
    return build_specs(model_defs(cfg), rules)


def model_shapes(cfg: ModelConfig):
    """ShapeDtypeStruct stand-ins (allocation-free dry-run)."""
    return build_shapes(model_defs(cfg))


def count_params_exact(cfg: ModelConfig) -> int:
    return param_count(model_defs(cfg))


def count_active_params_exact(cfg: ModelConfig) -> int:
    """Per-token active params (MoE experts scaled to experts_per_token)."""
    total = 0
    for path, leaf in _iter_defs(model_defs(cfg)):
        n = math.prod(leaf.shape)
        if "moe" in path and path[-1] in ("gate", "up", "down"):
            cfg_e = cfg.num_experts
            n = n * cfg.experts_per_token // cfg_e
        total += n
    return total


def _iter_defs(tree, path=()):
    if is_def(tree):
        yield path, tree
        return
    for k, v in tree.items():
        yield from _iter_defs(v, path + (k,))


# ---------------------------------------------------------------------------
# Block application (shared by forward / prefill / decode)
# ---------------------------------------------------------------------------


def _apply_block_full(cfg, run, j, blk, h, positions, rules, want_cache, max_len, inference=False):
    kind = cfg.layer_kind(j)
    aux: dict[str, jax.Array] = {}
    cache: dict[str, Any] = {}
    eps = cfg.norm_eps
    if kind == "attn":
        hn = rms_norm(h, blk["norm"], eps)
        if want_cache:
            y, (k, v) = attn.attn_apply_full(cfg, run, blk["attn"], hn, positions, rules, return_kv=True)
            fresh = attn.attn_init_cache(cfg, h.shape[0], max_len, jnp.dtype(cfg.compute_dtype))
            cache["attn"] = attn.attn_fill_cache(cfg, fresh, k, v)
        else:
            y = attn.attn_apply_full(cfg, run, blk["attn"], hn, positions, rules)
        h = h + y
    elif kind == "mamba":
        hn = rms_norm(h, blk["norm"], eps)
        if want_cache:
            y, mcache = _mamba_full_with_cache(cfg, run, blk["mamba"], hn, rules)
            cache["mamba"] = mcache
        else:
            y = ssm.mamba_apply_full(cfg, blk["mamba"], hn, rules, chunk=run.ssd_chunk, unroll=run.scan_unroll)
        h = h + y
    elif kind == "mlstm":
        if want_cache:
            y, state = _mlstm_full_with_cache(cfg, run, blk["mlstm"], h, rules)
            cache["mlstm"] = state
        else:
            y = ssm.mlstm_apply_full(cfg, blk["mlstm"], h, rules, chunk=run.ssd_chunk, unroll=run.scan_unroll)
        h = h + y
    elif kind == "slstm":
        if want_cache:
            y, state = ssm.slstm_apply_full(cfg, blk["slstm"], h, rules, return_state=True)
            cache["slstm"] = {"state": state}
        else:
            y = ssm.slstm_apply_full(cfg, blk["slstm"], h, rules)
        h = h + y

    if "moe" in blk:
        hn = rms_norm(h, blk["ffn_norm"], eps)
        y, moe_aux = moe_lib.moe_apply(cfg, blk["moe"], hn, rules, inference=inference)
        aux.update(moe_aux)
        h = h + y
    elif "ffn" in blk:
        hn = rms_norm(h, blk["ffn_norm"], eps)
        h = h + mlp_apply(blk["ffn"], hn, jnp.dtype(cfg.compute_dtype))
    h = shard_constraint(h, rules, ("batch", "sp", None))
    return h, aux, cache


def _mamba_full_with_cache(cfg, run, params, x, rules):
    """Full mamba pass that also returns the decode cache (conv + ssm state)."""
    dt_ = jnp.dtype(cfg.compute_dtype)
    # re-run the projection path capturing conv states
    z = x @ params["wz"].astype(dt_)
    xin_raw = x @ params["wx"].astype(dt_)
    b_raw = x @ params["wb"].astype(dt_)
    c_raw = x @ params["wc"].astype(dt_)
    dt_raw = x @ params["wdt"].astype(dt_)
    xin, cs_x = ssm._causal_conv(xin_raw, params["conv_x"].astype(dt_))
    bmat, cs_b = ssm._causal_conv(b_raw, params["conv_b"].astype(dt_))
    cmat, cs_c = ssm._causal_conv(c_raw, params["conv_c"].astype(dt_))
    xin, bmat, cmat = jax.nn.silu(xin), jax.nn.silu(bmat), jax.nn.silu(cmat)
    B, S, _ = x.shape
    H, P, N = ssm.mamba_heads(cfg), ssm.MAMBA_HEAD_DIM, cfg.d_state
    dt, loga = ssm._mamba_gates(cfg, params, xin, dt_raw)
    xh = xin.reshape(B, S, H, P)
    xh = shard_constraint(xh, rules, ("batch", None, "tp", None))
    bh = jnp.broadcast_to(bmat[:, :, None, :], (B, S, H, N)) * dt[..., None]
    ch = jnp.broadcast_to(cmat[:, :, None, :], (B, S, H, N))
    y, h_final = ssm.chunked_ssd(xh, loga, bh.astype(dt_), ch.astype(dt_), chunk=run.ssd_chunk, unroll=run.scan_unroll)
    y = y + params["d_skip"].astype(dt_)[None, None, :, None] * xh
    y = y.reshape(B, S, H * P)
    y = rms_norm(y, params["gate_norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = y @ params["wo"].astype(dt_)
    cache = {"conv_x": cs_x, "conv_b": cs_b, "conv_c": cs_c, "ssm": h_final}
    return out, cache


def _mlstm_full_with_cache(cfg, run, params, x, rules):
    dt_ = jnp.dtype(cfg.compute_dtype)
    B, S, D = x.shape
    H, hd = cfg.num_heads, cfg.head_dim_
    q, k, v, loga, igate = ssm._mlstm_qkv_gates(cfg, params, x)
    ones = jnp.ones((B, S, H, 1), dt_)
    v_aug = jnp.concatenate([v, ones], axis=-1)
    b = k * igate[..., None]
    y_aug, h_final = ssm.chunked_ssd(v_aug, loga, b, q, chunk=run.ssd_chunk, unroll=run.scan_unroll)
    y = ssm._mlstm_read(y_aug)
    y = y.reshape(B, S, H * hd)
    y = rms_norm(y, params["head_norm"], cfg.norm_eps)
    h = x + (y @ params["wo"].astype(dt_))
    hn = rms_norm(h, params["proj_norm"], cfg.norm_eps)
    g = jax.nn.silu(hn @ params["up_gate"].astype(dt_)) * (hn @ params["up"].astype(dt_))
    out = (g @ params["down"].astype(dt_)) + (h - x)
    return out, {"state": h_final}


def _apply_block_step(cfg, run, j, blk, cache_j, h, pos, rules):
    kind = cfg.layer_kind(j)
    eps = cfg.norm_eps
    new_cache: dict[str, Any] = {}
    if kind == "attn":
        hn = rms_norm(h, blk["norm"], eps)
        y, c = attn.attn_apply_step(cfg, run, blk["attn"], cache_j["attn"], hn, pos, rules)
        new_cache["attn"] = c
        h = h + y
    elif kind == "mamba":
        hn = rms_norm(h, blk["norm"], eps)
        y, c = ssm.mamba_apply_step(cfg, blk["mamba"], cache_j["mamba"], hn, rules)
        new_cache["mamba"] = c
        h = h + y
    elif kind == "mlstm":
        y, c = ssm.mlstm_apply_step(cfg, blk["mlstm"], cache_j["mlstm"], h, rules)
        new_cache["mlstm"] = c
        h = h + y
    elif kind == "slstm":
        y, c = ssm.slstm_apply_step(cfg, blk["slstm"], cache_j["slstm"], h, rules)
        new_cache["slstm"] = c
        h = h + y

    if "moe" in blk:
        hn = rms_norm(h, blk["ffn_norm"], eps)
        y, _ = moe_lib.moe_apply(cfg, blk["moe"], hn, rules, inference=True)
        h = h + y
    elif "ffn" in blk:
        hn = rms_norm(h, blk["ffn_norm"], eps)
        h = h + mlp_apply(blk["ffn"], hn, jnp.dtype(cfg.compute_dtype))
    return h, new_cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens, rules, prefix_features=None):
    dt_ = jnp.dtype(cfg.compute_dtype)
    h = params["embed"].astype(dt_)[tokens]
    if prefix_features is not None:
        pf = prefix_features.astype(dt_) @ params["frontend"]["proj"].astype(dt_)
        h = jnp.concatenate([pf, h], axis=1)
    return shard_constraint(h, rules, ("batch", "sp", None))


def _head(cfg, params, h, rules):
    dt_ = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].astype(dt_).T if cfg.tie_embeddings else params["lm_head"].astype(dt_)
    logits = h @ w
    logits = softcap(logits, cfg.logit_softcap)
    return shard_constraint(logits, rules, ("batch", "sp", "tp"))


def _remat(run: RunConfig, fn):
    if run.remat == "none":
        return fn
    if run.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # full


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    run: RunConfig,
    params: dict,
    tokens: jax.Array,
    rules: Optional[ShardingRules] = None,
    prefix_features: Optional[jax.Array] = None,
):
    """Training/eval forward. tokens: (B, S_text). Returns (logits, aux)."""
    h = _embed(cfg, params, tokens, rules, prefix_features)
    positions = jnp.arange(h.shape[1])[None, :]
    p = cfg.period

    def body(h, pparams):
        auxes = {}
        for j in range(p):
            h, aux, _ = _apply_block_full(
                cfg, run, j, pparams[f"b{j}"], h, positions, rules, False, 0
            )
            for k_, v_ in aux.items():
                auxes[k_] = auxes.get(k_, 0.0) + v_
        if not auxes:
            auxes = {"moe_aux": jnp.zeros(()), "moe_drop_frac": jnp.zeros(())}
        return h, auxes

    h, auxes = jax.lax.scan(_remat(run, body), h, params["layers"], unroll=run.scan_unroll)
    aux = {k_: jnp.mean(v_) for k_, v_ in auxes.items()}
    logits = _head(cfg, params, h, rules)
    return logits, aux


def prefill(
    cfg: ModelConfig,
    run: RunConfig,
    params: dict,
    tokens: jax.Array,
    max_len: int,
    rules: Optional[ShardingRules] = None,
    prefix_features: Optional[jax.Array] = None,
):
    """Forward + cache build. Returns (last-position logits, cache)."""
    h = _embed(cfg, params, tokens, rules, prefix_features)
    seq = h.shape[1]
    positions = jnp.arange(seq)[None, :]
    p = cfg.period

    def body(h, pparams):
        caches = {}
        for j in range(p):
            h, _, cache = _apply_block_full(
                cfg, run, j, pparams[f"b{j}"], h, positions, rules, True, max_len, inference=True
            )
            caches[f"b{j}"] = cache
        return h, caches

    h, layer_caches = jax.lax.scan(body, h, params["layers"], unroll=run.scan_unroll)
    logits = _head(cfg, params, h[:, -1:], rules)
    # per-slot position vector: every row of a fresh prefill sits at `seq`,
    # but rows diverge once the cache joins a continuous decode batch
    cache = {
        "pos": jnp.full((h.shape[0],), seq, jnp.int32),
        "layers": layer_caches,
    }
    return logits, cache


def decode_step(
    cfg: ModelConfig,
    run: RunConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,
    rules: Optional[ShardingRules] = None,
    active: Optional[jax.Array] = None,
):
    """One decode step. tokens: (B, 1). Returns (logits, new cache).

    ``cache["pos"]`` is a per-slot (B,) position vector, so rows of the
    batch may sit at different cache positions (continuous batching).
    ``active`` is an optional (B,) bool mask for ragged batches: inactive
    slots neither advance their position nor overwrite their cache slot
    (their logits are garbage the caller ignores; a slot-arena caller
    re-prefills a slot on join, so parked slots stay cheap, not correct).
    """
    h = _embed(cfg, params, tokens, rules)
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), (tokens.shape[0],))
    step_pos = pos if active is None else jnp.where(active, pos, -1)
    p = cfg.period

    def body(h, xs):
        pparams, pcache = xs
        new_caches = {}
        for j in range(p):
            h, c = _apply_block_step(
                cfg, run, j, pparams[f"b{j}"], pcache[f"b{j}"], h, step_pos, rules
            )
            new_caches[f"b{j}"] = c
        return h, new_caches

    h, new_layer_caches = jax.lax.scan(body, h, (params["layers"], cache["layers"]), unroll=run.scan_unroll)
    logits = _head(cfg, params, h, rules)
    new_pos = pos + 1 if active is None else pos + active.astype(jnp.int32)
    return logits, {"pos": new_pos, "layers": new_layer_caches}


# ---------------------------------------------------------------------------
# Cache construction / specs
# ---------------------------------------------------------------------------


def _block_cache_template(cfg: ModelConfig, j: int, batch: int, max_len: int):
    dt_ = jnp.dtype(cfg.compute_dtype)
    kind = cfg.layer_kind(j)
    if kind == "attn":
        return {"attn": attn.attn_init_cache(cfg, batch, max_len, dt_)}
    if kind == "mamba":
        return {"mamba": ssm.mamba_init_cache(cfg, batch, dt_)}
    if kind == "mlstm":
        return {"mlstm": ssm.mlstm_init_cache(cfg, batch, dt_)}
    if kind == "slstm":
        return {"slstm": ssm.slstm_init_cache(cfg, batch, dt_)}
    raise ValueError(kind)


def _block_cache_axes(cfg: ModelConfig, j: int):
    kind = cfg.layer_kind(j)
    if kind == "attn":
        return {"attn": attn.attn_cache_axes()}
    if kind == "mamba":
        return {"mamba": ssm.mamba_cache_axes()}
    if kind == "mlstm":
        return {"mlstm": ssm.mlstm_cache_axes()}
    if kind == "slstm":
        return {"slstm": ssm.slstm_cache_axes()}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Zero-filled cache (decode-from-scratch or dry-run stand-in)."""
    p = cfg.period

    def stack(leaf):
        return jnp.broadcast_to(leaf[None], (cfg.num_periods,) + leaf.shape).copy()

    layers = {
        f"b{j}": jax.tree.map(stack, _block_cache_template(cfg, j, batch, max_len))
        for j in range(p)
    }
    return {"pos": jnp.zeros((batch,), jnp.int32), "layers": layers}


def cache_specs(cfg: ModelConfig, rules: Optional[ShardingRules], batch: int, max_len: int):
    """PartitionSpec tree matching init_cache output."""
    from jax.sharding import PartitionSpec as P

    p = cfg.period
    layers = {}
    for j in range(p):
        template = _block_cache_template(cfg, j, batch, max_len)
        axes = _block_cache_axes(cfg, j)
        layers[f"b{j}"] = _spec_tree(template, axes, rules)
    return {"pos": P() if rules is None else P(), "layers": layers}


def _spec_tree(template, axes, rules):
    from jax.sharding import PartitionSpec as P

    out = {}
    for k, v in template.items():
        ax = axes[k]
        if isinstance(v, dict):
            out[k] = _spec_tree(v, ax, rules)
        elif isinstance(v, tuple):  # slstm state tuple
            out[k] = tuple(
                P() if rules is None else rules.spec((None,) + tuple(a), (0,) + leaf.shape)
                for leaf, a in zip(v, ax)
            )
        else:
            out[k] = P() if rules is None else rules.spec((None,) + tuple(ax), (0,) + v.shape)
    return out


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(
    cfg: ModelConfig,
    run: RunConfig,
    logits: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array],
    aux: dict,
):
    """Causal-LM cross entropy + z-loss + MoE aux. labels aligned to logits."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = (nll * mask).sum() / denom
    zl = run.z_loss * ((lse**2) * mask).sum() / denom
    total = ce + zl + run.moe_aux_loss * aux.get("moe_aux", 0.0)
    metrics = {"loss": total, "ce": ce, "z_loss": zl, **aux}
    return total, metrics
