"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

The multi-pod mesh (2×16×16) supports a third strategy besides DP and
FSDP+TP: stage-partitioning the layer stack across pods, with activations
handed between stages via ``jax.lax.ppermute`` inside ``shard_map``. This is
the right choice when the cross-pod DCN link is too slow for FSDP gathers
(the Hadoop paper's scarce cross-rack bandwidth, §IV.a Table 1): a pipeline
moves only (microbatch × hidden) activations per hop instead of re-gathering
parameter shards.

Schedule: GPipe fill-drain with M microbatches over P stages. Each device
executes ``M + P − 1`` ticks; at tick t, stage s computes microbatch
``t − s`` when ``0 ≤ t − s < M``. Bubble fraction = (P−1)/(M+P−1).

All stages execute the same compiled body (SPMD); stage identity comes from
the mesh coordinate, parameters are stage-local (sharded on the leading
stage axis), and the tick loop runs as ``lax.fori_loop`` with a rotating
activation buffer. The body `fn(stage_params, x)` is typically one period
of the model (models/model.py body), but any pure fn works — kept generic
so tests can validate the schedule exactly against a sequential run.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_apply(
    fn: Callable,  # (stage_params, x) -> x   — one stage's computation
    stage_params,  # pytree with leading stage axis (P, ...)
    x: jax.Array,  # (M, B, ...) microbatched input
    mesh: Mesh,
    stage_axis: str = "pod",
) -> jax.Array:
    """Run x through all pipeline stages; returns (M, B, ...) outputs.

    Parameters live sharded over ``stage_axis``; activations rotate through
    the ring with one ppermute per tick. Output microbatch m carries the
    result after every stage has been applied in order.
    """
    num_stages = mesh.shape[stage_axis]
    m = x.shape[0]
    assert m >= 1

    def staged(params_local, x_local):
        # params_local: stage-local slice (1, ...); x_local: full (M, B, ...)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(stage_axis)
        ticks = m + num_stages - 1

        def tick(t, carry):
            buf, out = carry
            # stage s processes microbatch (t - s) if in range
            mb = t - stage
            active = (mb >= 0) & (mb < m)
            # stage 0 ingests fresh microbatches; others use the handed-off buf
            src = jnp.where(stage == 0, 1, 0)
            fresh = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(mb, 0, m - 1), axis=0, keepdims=False
            )
            inp = jnp.where(src == 1, fresh, buf)
            y = fn(params_local, inp)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            done_mb = t - (num_stages - 1)
            is_last = stage == num_stages - 1
            record = (done_mb >= 0) & (done_mb < m) & is_last
            out = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(done_mb, 0, m - 1), axis=0
                ),
                lambda o: o,
                out,
            )
            # hand activations downstream (ring; the wraparound value is
            # ignored by stage 0, which reads fresh input)
            buf = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % num_stages) for i in range(num_stages)],
            )
            return buf, out

        buf0 = jnp.zeros_like(x_local[0])
        out0 = jnp.zeros_like(x_local)
        _, out = jax.lax.fori_loop(0, ticks, tick, (buf0, out0))
        # every stage holds an `out` buffer but only the last stage's is
        # real — gather and select it so the output can be replicated
        if num_stages > 1:
            out = jax.lax.all_gather(out, stage_axis)[num_stages - 1]
        return out

    other_axes = [a for a in mesh.axis_names if a != stage_axis]
    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    return jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
