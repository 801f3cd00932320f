"""Mixture-of-experts FFN with GShard-style grouped dispatch (TPU-native).

Routing: softmax top-k with renormalized gate weights (Mixtral convention),
per-group expert capacity ``C = ceil(group * k * capacity_factor / E)`` and
one-hot dispatch/combine einsums — the MXU-friendly formulation that shards as
an all-to-all when the expert dimension is placed on the ``model`` mesh axis.

Expert-parallel rule (see parallel/sharding.py): when ``E % tp == 0`` the
expert dim is sharded over ``model`` (true EP, e.g. moonshot 64e, jamba 16e);
otherwise the expert dim replicates and the per-expert hidden dim shards over
``model`` (in-expert TP, e.g. Mixtral 8e on a 16-way axis).

Sequence grouping (``moe_group_size``) bounds dispatch FLOPs: the one-hot
einsums cost O(G · g² · k · cf · d) instead of O(S² k cf d) for the whole
sequence — the Hadoop paper's "block size" tuning rule applied to routing.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamDef, nrm
from repro.parallel.sharding import ShardingRules, shard_constraint


def moe_defs(cfg: ModelConfig) -> dict:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.ffn_dim
    # expert-dim sharding handled by ShardingRules.spec divisibility logic:
    # ("expert", "fsdp", None) degrades to replicated-expert when E % tp != 0,
    # in which case the f dim picks up "tp" instead.
    # Expert weights shard over `model` via the expert dim when divisible
    # (EP); otherwise they stay fsdp-sharded only and the model axis instead
    # shards the *capacity* dim of the expert activations (see moe_apply) —
    # expert compute becomes pure data-parallel over capacity slots, so the
    # only model-axis collective left is the combine reduce (§Perf log).
    return {
        "router": ParamDef((d, e), ("fsdp", None), nrm()),
        "gate": ParamDef((e, d, f), ("expert", "fsdp", None), nrm(fan_in_axis=1)),
        "up": ParamDef((e, d, f), ("expert", "fsdp", None), nrm(fan_in_axis=1)),
        "down": ParamDef((e, f, d), ("expert", None, "fsdp"), nrm(fan_in_axis=1)),
    }


def resolve_moe_axes(cfg: ModelConfig, rules: Optional[ShardingRules]):
    """Decide EP vs in-expert-TP for the current mesh (used by spec builder)."""
    if rules is None:
        return False
    return cfg.num_experts % max(1, rules.tp_size) == 0


def _top_k_routing(logits: jax.Array, k: int):
    """logits: (..., E) → (gates, index one-hots) for k slots."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)  # renorm
    return probs, top_p, top_i


def moe_apply(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    rules: Optional[ShardingRules],
    inference: bool = False,
):
    """x: (B, S, D) → (y, aux_metrics). Grouped GShard dispatch.

    ``inference=True`` uses the eval capacity factor: capacity-based token
    dropping is not causal (a token's fate depends on later tokens in its
    dispatch group), so prefill/decode run with enough headroom to keep
    prefill results consistent with incremental decoding.
    """
    dt = x.dtype
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    g = min(cfg.moe_group_size, s)
    assert s % g == 0, (s, g)
    ng = b * (s // g)
    xg = x.reshape(ng, g, d)

    logits = jnp.einsum("gsd,de->gse", xg, params["router"].astype(dt))
    probs, top_p, top_i = _top_k_routing(logits, k)

    cf = cfg.moe_eval_capacity_factor if inference else cfg.moe_capacity_factor
    cap = int(max(1, min(g, -(-g * k * cf // e))))  # ceil, ≤ group size
    # slot position of each (token, k) in its expert queue, group-local
    sel = jax.nn.one_hot(top_i, e, dtype=jnp.float32)  # (ng, g, k, E)
    flat = sel.reshape(ng, g * k, e)
    pos_in_e = (jnp.cumsum(flat, axis=1) - flat).reshape(ng, g, k, e)
    pos = (pos_in_e * sel).sum(-1)  # (ng, g, k)
    keep = pos < cap
    gates = top_p * keep  # dropped tokens lose their gate weight

    # dispatch tensor (ng, g, E, C): for each token/k slot, one-hot over (e, c).
    # Built in compute dtype: 0/1 values and top-k gates are exactly/safely
    # representable in bf16, and this tensor dominates MoE activation bytes.
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=dt)  # (ng, g, k, C)
    disp = jnp.einsum("gske,gskc->gsec", (sel * keep[..., None]).astype(dt), pos_oh)
    comb = jnp.einsum("gske,gskc,gsk->gsec", sel.astype(dt), pos_oh, gates.astype(dt))

    # expert/capacity sharding: "expert" takes the model axis when E divides
    # it (EP); otherwise the capacity dim does (dedupe logic in spec()).
    ec_axes = (None, "expert", "moe_tp", None)
    disp = shard_constraint(disp, rules, (None, None, "expert", "moe_tp"))
    # NOTE: constraining `comb` the same way was tried and REFUTED in the
    # §Perf loop (+15.6% collective bytes — XLA reshards the combine einsum);
    # comb stays unconstrained and follows the output's batch sharding.
    xe = jnp.einsum("gsec,gsd->gecd", disp, xg)  # (ng, E, C, D)
    xe = shard_constraint(xe, rules, ec_axes)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, params["gate"].astype(dt)))
    h = h * jnp.einsum("gecd,edf->gecf", xe, params["up"].astype(dt))
    h = shard_constraint(h, rules, ec_axes)
    ye = jnp.einsum("gecf,efd->gecd", h, params["down"].astype(dt))
    ye = shard_constraint(ye, rules, ec_axes)
    y = jnp.einsum("gsec,gecd->gsd", comb, ye)

    # GShard aux load-balance loss: E · Σ_e f_e · p̄_e   (per group, meaned)
    f_e = sel.sum(2).mean(1)  # fraction routed to e  (ng, E)
    p_e = probs.mean(1)  # mean router prob        (ng, E)
    aux = e * jnp.mean(jnp.sum(f_e * p_e, axis=-1))
    dropped = 1.0 - jnp.mean(keep)
    return y.reshape(b, s, d), {"moe_aux": aux, "moe_drop_frac": dropped}
