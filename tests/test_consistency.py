"""Prefill + incremental decode must reproduce the full forward pass —
the cache-correctness invariant for every block family (attn KV, SWA ring,
Mamba conv+state, mLSTM matrix state, sLSTM scalar state, MoE routing)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.models import model as M

pytestmark = pytest.mark.slow  # JAX-compile-heavy: deselected in the default tier-1 run

RUN = RunConfig(remat="none", attention_impl="xla", ssd_chunk=16)


def _nodrop(cfg):
    if not cfg.num_experts:
        return cfg
    cf = float(cfg.num_experts) / cfg.experts_per_token
    return dataclasses.replace(cfg, moe_capacity_factor=cf, moe_eval_capacity_factor=cf)


@pytest.mark.parametrize(
    "arch,tol",
    [
        ("internlm2-1.8b", 3e-5),
        ("qwen3-1.7b", 3e-5),
        ("mixtral-8x22b", 3e-5),  # exercises the SWA ring cache (S > window)
        ("jamba-1.5-large-398b", 5e-5),
        ("xlstm-1.3b", 1e-4),
        ("musicgen-medium", 3e-5),
    ],
)
def test_prefill_decode_matches_forward(arch, tol):
    cfg = _nodrop(get_config(arch).reduced(param_dtype="float32", compute_dtype="float32"))
    key = jax.random.PRNGKey(1)
    params = M.init_model(key, cfg)
    B, S = 2, 40  # > reduced sliding window (16) to exercise the ring
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)

    logits_full, _ = M.forward(cfg, RUN, params, tokens)
    split = S - 5
    logits_pre, cache = M.prefill(cfg, RUN, params, tokens[:, :split], max_len=S)
    assert (
        float(jnp.abs(logits_pre[:, 0] - logits_full[:, split - 1]).max()) < tol
    ), "prefill last-token logits diverge from forward"

    for t in range(split, S):
        logits_t, cache = M.decode_step(cfg, RUN, params, cache, tokens[:, t : t + 1])
        err = float(jnp.abs(logits_t[:, 0] - logits_full[:, t]).max())
        assert err < tol, f"decode step {t}: err {err}"
    assert cache["pos"].tolist() == [S] * B  # per-slot position vector


def test_decode_from_scratch_matches_forward():
    cfg = get_config("internlm2-1.8b").reduced(param_dtype="float32", compute_dtype="float32")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    B, S = 1, 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    logits_full, _ = M.forward(cfg, RUN, params, tokens)
    cache = M.init_cache(cfg, B, S)
    for t in range(S):
        logits_t, cache = M.decode_step(cfg, RUN, params, cache, tokens[:, t : t + 1])
        assert float(jnp.abs(logits_t[:, 0] - logits_full[:, t]).max()) < 3e-5


def test_attention_impls_agree():
    """xla / chunked / pallas(interpret) produce the same attention."""
    cfg = get_config("internlm2-1.8b").reduced(param_dtype="float32", compute_dtype="float32")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    outs = {}
    for impl in ("xla", "chunked", "pallas_interpret"):
        run = dataclasses.replace(RUN, attention_impl=impl, attention_chunk=32)
        outs[impl], _ = M.forward(cfg, run, params, tokens)
    assert float(jnp.abs(outs["xla"] - outs["chunked"]).max()) < 2e-5
    assert float(jnp.abs(outs["xla"] - outs["pallas_interpret"]).max()) < 2e-5


def test_decode_attention_impls_agree():
    """einsum (CPU fallback) vs Pallas flash-decode in interpret mode must
    agree bit-close on the serving decode step, including partially-filled
    caches and inactive rows — the tentpole's kernel-fallback contract."""
    cfg = get_config("qwen3-1.7b").reduced(param_dtype="float32", compute_dtype="float32")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    B, S = 3, 24
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    outs = {}
    for impl in ("einsum", "kernel_interpret"):
        run = dataclasses.replace(RUN, decode_attention_impl=impl)
        _, cache = M.prefill(cfg, run, params, tokens[:, :10], max_len=S)
        active = jnp.array([True, True, False])  # a parked arena slot
        logits = []
        for t in range(10, 14):
            lt, cache = M.decode_step(
                cfg, run, params, cache, tokens[:, t : t + 1], active=active
            )
            logits.append(lt)
        outs[impl] = jnp.stack(logits)
        assert cache["pos"].tolist() == [14, 14, 10]  # active mask honoured
    # the parked row's logits are garbage by contract (decode_step): the
    # kernel zeroes an all-invalid row, the einsum averages it uniformly
    err = float(jnp.abs(outs["einsum"][:, active] - outs["kernel_interpret"][:, active]).max())
    assert err < 2e-5, f"decode impl divergence: {err}"


def test_chunked_ssd_matches_sequential():
    from repro.kernels.ref import ssm_scan_ref
    from repro.models.ssm import chunked_ssd

    key = jax.random.PRNGKey(0)
    B, S, H, P, N = 2, 96, 3, 16, 8
    x = jax.random.normal(key, (B, S, H, P))
    loga = -jnp.abs(jax.random.normal(key, (B, S, H))) * 0.1
    b = jax.random.normal(key, (B, S, H, N)) * 0.3
    c = jax.random.normal(key, (B, S, H, N)) * 0.3
    y1, h1 = chunked_ssd(x, loga, b, c, chunk=32)
    y2, h2 = ssm_scan_ref(x, loga, b, c)
    assert float(jnp.abs(y1 - y2).max()) < 1e-4
    assert float(jnp.abs(h1 - h2).max()) < 1e-4
