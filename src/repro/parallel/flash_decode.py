"""Cross-chip flash-decode: KV cache sharded by sequence over ``model``.

Each shard runs the Pallas decode kernel over its local cache slice,
producing unnormalized partials (out, m, l); the combine is a logsumexp
reduction over the mesh axis (pmax for the running max, psum for the
rescaled numerator/denominator) — three tiny collectives of (B, H[, D])
instead of gathering the cache.

This is the explicit shard_map twin of what GSPMD derives automatically for
the jnp decode path (models/attention.py); it exists so the TPU kernel can
be used under manual partitioning and is validated against the jnp result
in tests/test_flash_decode.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kops


def sharded_decode_attention(
    q: jax.Array,  # (B, H, D) — replicated over the seq-shard axis
    k: jax.Array,  # (B, S, KH, D) — S sharded over `axis`
    v: jax.Array,
    valid: jax.Array,  # (B, S) bool
    mesh: Mesh,
    axis: str = "model",
    batch_axes: Optional[tuple[str, ...]] = ("data",),
    use_kernel: bool = True,
    interpret: bool = True,
) -> jax.Array:
    """Exact attention over a sequence-sharded KV cache."""
    bspec = batch_axes if batch_axes and all(a in mesh.axis_names for a in (batch_axes or ())) else None

    def local(q_l, k_l, v_l, valid_l):
        if use_kernel:
            out, m, l = kops.decode_attention(
                q_l, k_l, v_l, valid_l, return_partials=True, interpret=interpret
            )
        else:  # jnp partials fallback
            b, h, d = q_l.shape
            kh = k_l.shape[2]
            g = h // kh
            qg = q_l.reshape(b, kh, g, d).astype(jnp.float32)
            s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_l.astype(jnp.float32))
            s = s / (d**0.5)
            s = jnp.where(valid_l[:, None, None, :], s, -1e30)
            m = s.max(-1)
            p = jnp.exp(s - m[..., None])
            l = p.sum(-1)
            out = jnp.einsum("bhgk,bkhd->bhgd", p, v_l.astype(jnp.float32))
            out = out.reshape(b, h, d)
            m, l = m.reshape(b, h), l.reshape(b, h)
        # logsumexp combine across sequence shards
        m_g = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_g)
        num = jax.lax.psum(out * w[..., None], axis)
        den = jax.lax.psum(l * w, axis)
        return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q_l.dtype)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),
            P(bspec, axis, None, None),
            P(bspec, axis, None, None),
            P(bspec, axis),
        ),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(q, k, v, valid)
