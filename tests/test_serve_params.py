"""The parameter tree the serving step programs read
(``models.model.serving_params``): every matrix cast once to the compute
dtype when ``ServeLoop`` is built, so no prefill or decode call converts a
weight again. The cast must change no served bit: the matmuls read the
same bf16 values they read when the program converted them itself."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.data.dataset import SyntheticCorpus
from repro.launch.fleet import build_fleet
from repro.launch.serve import Request, ServeLoop
from repro.models import model as M

RUN = RunConfig(remat="none", attention_impl="xla", ssd_chunk=8)
# one per family of configs/: dense with and without qk-norm, sparse MoE
# (mixtral, moonshot), hybrid attention/mamba/MoE (jamba), sLSTM/mLSTM
FAMILIES = ("qwen3-1.7b", "internlm2-1.8b", "mixtral-8x22b", "moonshot-v1-16b-a3b",
            "jamba-1.5-large-398b", "xlstm-1.3b")
# per-layer vectors, left in their dtype: those the model reads in float32
# (rms_norm gains, qk-norm, the SSM's decay and step bias) ...
READ_IN_F32 = {"norm", "ffn_norm", "final_norm", "q_norm", "k_norm", "gate_norm",
               "mixer_norm", "head_norm", "proj_norm", "out_norm", "a_log", "dt_bias"}
# ... and those it casts where it reads them (a few hundred bytes a layer)
CAST_AT_USE = {"d_skip", "bi", "bf", "bz", "bo"}
PROMPT, STEPS = 8, 3
MAX_LEN = 16


def _cfg(arch):
    cfg = get_config(arch)
    return cfg.reduced(num_layers=max(2, cfg.period), d_model=64, vocab_size=64)


@pytest.fixture(scope="module")
def trees():
    """(cfg, float32 tree) per family, made once."""
    out = {}

    def get(arch):
        if arch not in out:
            cfg = _cfg(arch)
            out[arch] = (cfg, M.init_model(jax.random.PRNGKey(0), cfg))
        return out[arch]

    return get


def _leaves(tree):
    """(path of keys, per-layer rank, array) for every leaf."""
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        keys = tuple(k.key for k in path)
        yield keys, x.ndim - (keys[0] == "layers"), x


def _prefill_and_decode(cfg, params, tokens):
    """Logits of a prefill of ``tokens[:, :PROMPT]`` and of one decode step
    for each later token, fed the same tokens whatever the logits say."""
    pre = jax.jit(partial(M.prefill, cfg, RUN, max_len=MAX_LEN))
    dec = jax.jit(partial(M.decode_step, cfg, RUN))
    logits, cache = pre(params, tokens[:, :PROMPT])
    out = [logits]
    for i in range(PROMPT, PROMPT + STEPS):
        logits, cache = dec(params, cache, tokens[:, i:i + 1])
        out.append(logits)
    return np.asarray(jnp.concatenate(out, axis=1), np.float32)


@pytest.mark.parametrize("arch", FAMILIES)
def test_served_logits_equal_the_float32_trees(trees, arch):
    cfg, p = trees(arch)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, PROMPT + STEPS), 0, cfg.vocab_size)
    want = _prefill_and_decode(cfg, p, tokens)
    got = _prefill_and_decode(cfg, M.serving_params(cfg, p), tokens)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("arch", FAMILIES)
def test_matrices_in_compute_dtype_vectors_as_they_were(trees, arch):
    cfg, p = trees(arch)
    served = M.serving_params(cfg, p)
    dt = jnp.dtype(cfg.compute_dtype)
    kept = set()
    for (keys, rank, x), (_, _, y) in zip(_leaves(served), _leaves(p)):
        if rank >= 2:
            assert x.dtype == dt, keys
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y.astype(dt)))
        else:
            assert x is y, keys
            kept.add(keys[-1])
    assert kept, "every family has norm gains"
    assert kept <= READ_IN_F32 | CAST_AT_USE, kept - READ_IN_F32 - CAST_AT_USE


def _requests(vocab, n=6, gen=5):
    lens = (6, 9, 12)
    corpus = SyntheticCorpus(vocab, max(lens), 0)
    return [Request(i, corpus.grain_tokens(i, 1)[0][: lens[i % len(lens)]], gen)
            for i in range(n)]


def _greedy(cfg, params, prompt, n):
    """The model called directly, one request at a time."""
    pre = jax.jit(partial(M.prefill, cfg, RUN, max_len=MAX_LEN * 2))
    dec = jax.jit(partial(M.decode_step, cfg, RUN))
    logits, cache = pre(params, np.asarray(prompt[None], np.int32))
    out = [int(jnp.argmax(logits[0, -1]))]
    while len(out) < n:
        logits, cache = dec(params, cache, np.asarray([[out[-1]]], np.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def test_arena_serves_the_tokens_of_the_float32_model(trees):
    cfg, p = trees("qwen3-1.7b")
    loop = ServeLoop(cfg, RUN, p, batch=4, max_len=MAX_LEN * 2, mode="arena")
    reqs = _requests(cfg.vocab_size)
    stats = loop.run_requests(reqs)
    assert stats["completed"] == len(reqs)
    assert [r.tokens for r in reqs] == [_greedy(cfg, p, r.prompt, r.max_new) for r in reqs]


F32_CONVERT = re.compile(r"stablehlo\.convert \S+ : \(tensor<([0-9x]+)xf32>\)")


def test_step_programs_convert_no_weight_matrix(trees):
    cfg, p = trees("qwen3-1.7b")
    batch = 4
    loop = ServeLoop(cfg, RUN, p, batch=batch, max_len=MAX_LEN, mode="arena")
    shapes = set()
    for _, rank, x in _leaves(p):
        if rank >= 2:  # whole stacks and the per-layer slices the scan reads
            shapes |= {x.shape, x.shape[1:] if x.ndim > rank else x.shape}
    weight = {"x".join(map(str, s)) for s in shapes}
    arena = M.init_cache(cfg, batch, MAX_LEN)
    programs = {
        "decode": loop._decode_arena.lower(loop.params, arena, np.zeros((batch, 1), np.int32),
                                           np.ones(batch, bool)),
        "prefill": loop.prefill.lower(loop.params, np.zeros((1, PROMPT), np.int32)),
    }
    for name, lowered in programs.items():
        found = [s for s in F32_CONVERT.findall(lowered.as_text()) if s in weight]
        assert not found, f"{name} converts float32 weights of shapes {found}"


def test_loop_builds_without_weights():
    cfg = _cfg("qwen3-1.7b")
    loop = ServeLoop(cfg, RUN, None, batch=2, max_len=MAX_LEN, mode="arena")
    assert loop.params is None


def test_served_param_bytes_count_the_tree(trees):
    cfg, p = trees("qwen3-1.7b")
    loop = ServeLoop(cfg, RUN, p, batch=2, max_len=MAX_LEN * 2, mode="arena")
    loop.run_requests(_requests(cfg.vocab_size, n=2, gen=2))
    got = loop.stats()["served_param_bytes"]
    leaves = list(_leaves(p))
    assert got == {
        "bfloat16": sum(2 * x.size for _, rank, x in leaves if rank >= 2),
        "float32": sum(4 * x.size for _, rank, x in leaves if rank < 2),
    }
    assert sum(got.values()) == sum(x.nbytes for x in jax.tree.leaves(loop.params))


def test_fleet_replicas_on_one_device_share_one_served_tree(trees):
    cfg, p = trees("qwen3-1.7b")
    n = len(jax.devices())  # replicas 0 and n both sit on device 0
    fleet = build_fleet(cfg, RUN, p, n + 1, batch=2, max_len=MAX_LEN, mode="arena")
    a, b = fleet.replicas[0], fleet.replicas[n]
    assert all(x is y for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)))
    assert a.params["embed"].dtype == jnp.dtype(cfg.compute_dtype)
