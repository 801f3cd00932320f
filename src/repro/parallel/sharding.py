"""Logical-axis sharding rules for the (pod, data, model) production mesh.

Every parameter / activation axis in the model is annotated with a *logical*
axis name; this module maps logical names to physical mesh axes. The mapping
adapts to whatever mesh is active (single-pod ``(data, model)``, multi-pod
``(pod, data, model)``, or no mesh at all during CPU unit tests, in which case
all constraints become no-ops).

Logical axes
------------
``batch``    data-parallel batch → all DP axes ("pod","data")
``fsdp``     parameter shard axis for ZeRO-3 → all DP axes (or None w/o FSDP)
``tp``       tensor-parallel → "model"
``sp``       sequence-parallel activations → "model"
``expert``   MoE expert-parallel → "model" when divisible, else None
``kv_seq``   decode KV-cache sequence shards → "model" (flash-decode)
``null``     explicit replication
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class Axes:
    """Physical mesh-axis names, in order."""

    names: tuple[str, ...]

    @property
    def dp(self) -> tuple[str, ...]:
        return tuple(a for a in self.names if a in ("pod", "data"))

    @property
    def has_model(self) -> bool:
        return "model" in self.names


@dataclass(frozen=True)
class ShardingRules:
    """Logical→physical mapping, derived from the active mesh + run flags."""

    mesh_axes: tuple[str, ...]
    mesh_shape: tuple[int, ...]
    fsdp: bool = True
    sequence_parallel: bool = True

    # ------------------------------------------------------------------
    def axis_size(self, name: str) -> int:
        if name not in self.mesh_axes:
            return 1
        return self.mesh_shape[self.mesh_axes.index(name)]

    @property
    def dp_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.mesh_axes if a in ("pod", "data"))

    @property
    def dp_size(self) -> int:
        s = 1
        for a in self.dp_axes:
            s *= self.axis_size(a)
        return s

    @property
    def tp_size(self) -> int:
        return self.axis_size("model")

    # ------------------------------------------------------------------
    def resolve(self, logical: Optional[str], dim_size: Optional[int] = None):
        """Map one logical axis name to a physical axis (or None)."""
        if logical is None or logical == "null":
            return None
        if logical == "batch":
            if not self.dp_axes:
                return None
            if dim_size is not None and dim_size % self.dp_size != 0:
                return None  # e.g. global_batch=1 long-context decode
            return self.dp_axes
        if logical == "fsdp":
            if not self.fsdp or not self.dp_axes:
                return None
            if dim_size is not None and dim_size % self.dp_size != 0:
                return None  # indivisible → replicate rather than crash
            return self.dp_axes
        if logical in ("tp", "sp", "expert", "kv_seq", "moe_tp"):
            if logical == "sp" and not self.sequence_parallel:
                return None
            if "model" not in self.mesh_axes:
                return None
            if dim_size is not None and dim_size % self.tp_size != 0:
                return None
            return "model"
        raise ValueError(f"unknown logical axis {logical!r}")

    def spec(
        self,
        logical_axes: Sequence[Optional[str]],
        shape: Optional[Sequence[int]] = None,
    ) -> P:
        """Build a PartitionSpec from per-dimension logical names.

        If ``shape`` is given, any logical axis whose physical axis size does
        not divide the dimension is dropped (replicated) — this is what makes
        e.g. Mixtral's 8 experts on a 16-way model axis degrade gracefully to
        expert-dim replication + in-expert TP (see models/moe.py).
        """
        phys = []
        for i, name in enumerate(logical_axes):
            dim = None if shape is None else shape[i]
            phys.append(self.resolve(name, dim))
        # PartitionSpec forbids using the same mesh axis twice — keep first.
        used: set[str] = set()
        out = []
        for p in phys:
            axes = (p,) if isinstance(p, str) else tuple(p or ())
            if any(a in used for a in axes):
                out.append(None)
                continue
            used.update(axes)
            out.append(p)
        return P(*out)


# ---------------------------------------------------------------------------
# Constraint helpers (mesh-optional: no-ops without an active mesh)
# ---------------------------------------------------------------------------


def _active_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def rules_from_mesh(mesh: Mesh, fsdp: bool = True, sequence_parallel: bool = True) -> ShardingRules:
    return ShardingRules(
        mesh_axes=tuple(mesh.axis_names),
        mesh_shape=tuple(mesh.devices.shape),
        fsdp=fsdp,
        sequence_parallel=sequence_parallel,
    )


def logical_spec(rules: Optional[ShardingRules], logical_axes, shape=None) -> P:
    if rules is None:
        return P()
    return rules.spec(logical_axes, shape)


def shard_constraint(x, rules: Optional[ShardingRules], logical_axes):
    """`with_sharding_constraint` that degrades to identity off-mesh."""
    if rules is None:
        return x
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = rules.spec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, rules: ShardingRules, logical_axes, shape=None) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(logical_axes, shape))
