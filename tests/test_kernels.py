"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment requirement c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _arr(rng, *shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


FLASH_CASES = [
    # (B, Sq, Sk, H, KH, D, window, q_offset, bq, bk)
    (2, 128, 128, 4, 2, 64, 0, 0, 64, 64),
    (1, 100, 256, 8, 8, 128, 0, 156, 64, 64),  # ragged + offset (prefill tail)
    (2, 256, 256, 6, 2, 64, 64, 0, 64, 64),  # sliding window
    (1, 64, 64, 2, 1, 256, 0, 0, 32, 32),  # big head dim
    (1, 33, 65, 4, 4, 64, 0, 0, 32, 32),  # non-divisible seq (padding)
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype, rng):
    B, Sq, Sk, H, KH, D, win, off, bq, bk = case
    q = _arr(rng, B, Sq, H, D, dtype=dtype)
    k = _arr(rng, B, Sk, KH, D, dtype=dtype)
    v = _arr(rng, B, Sk, KH, D, dtype=dtype)
    out = ops.flash_attention(q, k, v, True, off, win, None, bq, bk, True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=win, q_offset=off)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)).max()) < tol


def test_flash_attention_grad_matches_ref(rng):
    q = _arr(rng, 1, 64, 4, 64)
    k = _arr(rng, 1, 64, 2, 64)
    v = _arr(rng, 1, 64, 2, 64)

    def f_kernel(q, k, v):
        return ops.flash_attention(q, k, v, True, 0, 0, None, 32, 32, True).sum()

    def f_ref(q, k, v):
        return ref.flash_attention_ref(q, k, v).astype(jnp.float32).sum()

    for g, ge in zip(jax.grad(f_kernel, (0, 1, 2))(q, k, v), jax.grad(f_ref, (0, 1, 2))(q, k, v)):
        assert float(jnp.abs(g - ge).max()) < 1e-4


DECODE_CASES = [
    (2, 512, 8, 2, 64, 128),
    (3, 300, 4, 4, 128, 128),  # padding + MHA
    (1, 1024, 16, 2, 64, 256),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(case, dtype, rng):
    B, S, H, KH, D, bk = case
    q = _arr(rng, B, H, D, dtype=dtype)
    k = _arr(rng, B, S, KH, D, dtype=dtype)
    v = _arr(rng, B, S, KH, D, dtype=dtype)
    valid = jnp.asarray(rng.random((B, S)) > 0.3)
    out = ops.decode_attention(q, k, v, valid, block_k=bk, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, valid)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)).max()) < tol


def test_decode_ring_wraparound_at_full_capacity(rng):
    """The serving arena's sliding-window rows mark the whole cache valid
    once pos >= capacity (ring fully wrapped) — all-True valid must agree
    with the reference at exactly-full capacity, both when S divides the
    block and when a zero-padded remainder block trails it."""
    B, H, KH, D = 2, 4, 2, 64
    for S, bk in ((256, 128), (130, 64)):  # exact blocks | remainder block
        q = _arr(rng, B, H, D)
        k = _arr(rng, B, S, KH, D)
        v = _arr(rng, B, S, KH, D)
        valid = jnp.ones((B, S), bool)
        out = ops.decode_attention(q, k, v, valid, block_k=bk, interpret=True)
        exp = ref.decode_attention_ref(q, k, v, valid)
        assert float(jnp.abs(out - exp).max()) < 2e-5, (S, bk)


def test_decode_valid_only_in_remainder_block(rng):
    """A row whose valid keys all live in the last (zero-padded) remainder
    block is the regression case for the masked-probability bug: while no
    valid key has been seen, masked entries exponentiate NEG_INF - NEG_INF
    to 1 and leak phantom mass into l/acc unless written as zero."""
    B, S, H, KH, D, bk = 2, 190, 4, 2, 64, 64  # 3 blocks, last holds 62 keys
    q = _arr(rng, B, H, D)
    k = _arr(rng, B, S, KH, D)
    v = _arr(rng, B, S, KH, D)
    idx = jnp.arange(S)
    valid = jnp.stack([idx >= 2 * bk, idx >= S - 5])  # tail-only valid rows
    out = ops.decode_attention(q, k, v, valid, block_k=bk, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, valid)
    assert float(jnp.abs(out - exp).max()) < 2e-5


def test_decode_all_invalid_row_returns_zero(rng):
    """Contract for a row with no valid keys (an arena slot before its
    prefill lands): the kernel emits exactly zero — never NaN/Inf — and its
    partials are the logsumexp identity (m = -inf surrogate, l = 0), so a
    cross-shard combine treats the row as contributing nothing. (The
    einsum/ref path instead softmaxes uniform over NEG_INF scores; callers
    mask inactive rows, so only finiteness is contractual there.)"""
    B, S, H, KH, D = 2, 128, 4, 2, 64
    q = _arr(rng, B, H, D)
    k = _arr(rng, B, S, KH, D)
    v = _arr(rng, B, S, KH, D)
    valid = jnp.stack([jnp.ones(S, bool), jnp.zeros(S, bool)])
    out = ops.decode_attention(q, k, v, valid, block_k=64, interpret=True)
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.abs(out[1]).max()) == 0.0
    exp = ref.decode_attention_ref(q, k, v, valid)
    assert float(jnp.abs(out[0] - exp[0]).max()) < 2e-5
    _, m, l = ops.decode_attention(
        q, k, v, valid, block_k=64, return_partials=True, interpret=True
    )
    assert float(l[1].max()) == 0.0  # partials come back (B, H): row 1 empty


def test_decode_mask_layout_edge_rows(rng):
    """The kernel's (B·KH, 1, S) mask and (B·KH, 1, G) m/l layout, through
    ops.decode_attention, on the edge rows of one call: a wrapped ring
    (every slot valid, a remainder block), a ring whose valid slots
    straddle the wrap point, a row valid only in the tail, and a parked
    row. Normalized output matches the reference on live rows and is zero
    on the parked one; partials keep their (B, H) contract with l = 0 on
    the parked row, and combining two sequence halves — one of them empty
    for the tail-only row — reproduces the monolithic result."""
    B, S, H, KH, D, bk = 4, 200, 4, 2, 64, 64
    q = _arr(rng, B, H, D)
    k = _arr(rng, B, S, KH, D)
    v = _arr(rng, B, S, KH, D)
    idx = jnp.arange(S)
    valid = jnp.stack(
        [jnp.ones(S, bool), (idx < 37) | (idx >= 150), idx >= S - 3, jnp.zeros(S, bool)]
    )
    exp = ref.decode_attention_ref(q, k, v, valid)
    out = ops.decode_attention(q, k, v, valid, block_k=bk, interpret=True)
    assert float(jnp.abs(out[:3] - exp[:3]).max()) < 2e-5
    assert float(jnp.abs(out[3]).max()) == 0.0

    o, m, l = ops.decode_attention(
        q, k, v, valid, block_k=bk, return_partials=True, interpret=True
    )
    assert m.shape == l.shape == (B, H)
    assert bool(jnp.isfinite(o).all()) and float(l[3].max()) == 0.0
    parts = [
        ops.decode_attention(
            q, k[:, sl], v[:, sl], valid[:, sl], block_k=bk,
            return_partials=True, interpret=True,
        )
        for sl in (slice(0, S // 2), slice(S // 2, S))
    ]
    combined = ops.combine_decode_partials(*zip(*parts))
    assert float(jnp.abs(combined[:3] - exp[:3]).max()) < 2e-5
    assert float(jnp.abs(combined[3]).max()) == 0.0


def test_decode_attention_impl_follows_platform(monkeypatch):
    """The decode attention is chosen from the platform: the kernel on a
    TPU, the einsum elsewhere; interpret mode is refused on a TPU."""
    from repro.models import attention

    assert attention.resolve_decode_impl("auto") == "einsum"  # tests run on the CPU
    assert attention.resolve_decode_impl("kernel_interpret") == "kernel_interpret"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.resolve_decode_impl("auto") == "kernel"
    with pytest.raises(ValueError):
        attention.resolve_decode_impl("kernel_interpret")


def test_decode_partials_combine(rng):
    """Shard the cache in two, combine partials, compare to monolithic."""
    B, S, H, KH, D = 2, 256, 4, 2, 64
    q = _arr(rng, B, H, D)
    k = _arr(rng, B, S, KH, D)
    v = _arr(rng, B, S, KH, D)
    valid = jnp.ones((B, S), bool)
    outs, ms, ls = [], [], []
    for sl in (slice(0, S // 2), slice(S // 2, S)):
        o, m, l = ops.decode_attention(
            q, k[:, sl], v[:, sl], valid[:, sl], return_partials=True, interpret=True
        )
        outs.append(o), ms.append(m), ls.append(l)
    combined = ops.combine_decode_partials(outs, ms, ls)
    exp = ref.decode_attention_ref(q, k, v, valid)
    assert float(jnp.abs(combined - exp).max()) < 2e-5


SSM_CASES = [
    (2, 512, 4, 128, 64, 128),
    (1, 256, 2, 64, 32, 64),
    (2, 128, 8, 128, 16, 128),  # single chunk
]


@pytest.mark.parametrize("case", SSM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_matches_ref(case, dtype, rng):
    B, S, H, P, N, chunk = case
    x = _arr(rng, B, S, H, P, dtype=dtype)
    loga = -jnp.abs(_arr(rng, B, S, H)) * 0.1
    b = _arr(rng, B, S, H, N, dtype=dtype, scale=0.2)
    c = _arr(rng, B, S, H, N, dtype=dtype, scale=0.2)
    y, h = ops.ssm_scan(x, loga, b, c, chunk=chunk, interpret=True)
    ye, he = ref.ssm_scan_ref(x, loga, b, c)
    tol = 5e-3 if dtype == jnp.float32 else 5e-2
    assert float(jnp.abs(y.astype(jnp.float32) - ye.astype(jnp.float32)).max()) < tol
    assert float(jnp.abs(h - he).max()) < tol


def test_ssm_scan_state_carry_across_chunks(rng):
    """Final state from the kernel equals running the recurrence to the end."""
    B, S, H, P, N = 1, 64, 1, 8, 4
    x = _arr(rng, B, S, H, P)
    loga = -jnp.abs(_arr(rng, B, S, H)) * 0.05
    b = _arr(rng, B, S, H, N, scale=0.3)
    c = _arr(rng, B, S, H, N, scale=0.3)
    _, h16 = ops.ssm_scan(x, loga, b, c, chunk=16, interpret=True)
    _, h64 = ops.ssm_scan(x, loga, b, c, chunk=64, interpret=True)
    assert float(jnp.abs(h16 - h64).max()) < 1e-4
