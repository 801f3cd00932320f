"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# XLA flags we set on real TPU deployments for collective/compute overlap.
# (Harmless no-ops on CPU; recorded here so launch scripts share one source.)
TPU_PERF_FLAGS = [
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
]


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16×16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...] | None = None):
    """Arbitrary mesh for tests / small dry-runs.

    Axes are ``Auto``: the sharding rules place arrays through
    ``with_sharding_constraint`` and leave the rest to the partitioner,
    which ``jax.make_mesh``'s default ``Explicit`` axes would reject."""
    if axes is None:
        axes = {1: ("model",), 2: ("data", "model"), 3: ("pod", "data", "model")}[len(shape)]
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def parse_mesh_arg(arg: str):
    """'16x16' → single-pod-style mesh; '2x16x16' → multi-pod-style."""
    shape = tuple(int(x) for x in arg.lower().split("x"))
    return make_mesh(shape)
