"""The KV arena updated in place: ``ServeLoop`` hands the arena to its
decode step and its slot write to consume (donated), the decode scan
carries the stacked cache and writes one position per active row, and
only rules that shard the cache's sequence axis keep the whole-layer
select write. None of it may change a served bit: the tokens and the
cache contents equal those of the select write, an inactive row's cache
is left alone at every position, and every cache kind of a hybrid stack
decodes as a layer-by-layer reference on plain per-layer slices does."""

import dataclasses
import json
import os
import re
import subprocess
import sys
import textwrap
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.data.dataset import SyntheticCorpus
from repro.launch.serve import Request, ServeLoop
from repro.models import attention as attn
from repro.models import model as M
from repro.models import moe as moe_lib
from repro.models import ssm
from repro.parallel.sharding import ShardingRules

RUN = RunConfig(remat="none", attention_impl="xla", ssd_chunk=8)
DENSE = get_config("qwen3-1.7b").reduced(num_layers=3, d_model=64, vocab_size=64)
RING = dataclasses.replace(DENSE, sliding_window=8)
CAP = 16  # the arena's positions (the ring's capacity is its window, 8)
BATCH = 6
# a model axis of 2 shards the cache's sequence axis; with no mesh active
# every sharding constraint is a no-op, so only the write form differs
SEQ_SHARDED = ShardingRules(mesh_axes=("data", "model"), mesh_shape=(1, 2))
# rows parked, past the window or the capacity, and a mix of both
MASKS = {
    "all": [1, 1, 1, 1, 1, 1],
    "alternate": [1, 0, 1, 0, 1, 0],
    "one": [0, 0, 0, 0, 1, 0],
    "none": [0, 0, 0, 0, 0, 0],
}


def _params(cfg):
    return M.serving_params(cfg, M.init_model(jax.random.PRNGKey(0), cfg))


def _arena(cfg, pos, seed=1):
    """An arena of random K/V (so that a stray write shows) at positions ``pos``."""
    cache = M.init_cache(cfg, len(pos), CAP)
    leaves, tree = jax.tree.flatten(cache["layers"])
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    leaves = [jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype) for k, x in zip(keys, leaves)]
    return {"pos": jnp.asarray(pos, jnp.int32), "layers": jax.tree.unflatten(tree, leaves)}


def _step(cfg, rules=None):
    return jax.jit(lambda p, c, t, a: M.decode_step(cfg, RUN, p, c, t, rules, active=a))


def _kv(cache):
    """(name, stacked K or V) of every attention block."""
    return [(f"{b}/{n}", np.asarray(x, np.float32)) for b, blk in cache["layers"].items()
            if "attn" in blk for n, x in blk["attn"].items()]


def _assert_same(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("cfg", [DENSE, RING], ids=["full", "ring"])
def test_scatter_write_equals_the_select_write(cfg, mask):
    """Three steps over ragged rows, positions below and past the window:
    logits and the whole arena bit-equal to the rules' select write."""
    p = _params(cfg)
    act = np.asarray(MASKS[mask], bool)
    pos = [0, 3, 7, 9, 14, 15]
    ours, theirs = _step(cfg), _step(cfg, SEQ_SHARDED)
    a = b = _arena(cfg, pos)
    for i in range(3):
        toks = jax.random.randint(jax.random.PRNGKey(10 + i), (BATCH, 1), 0, cfg.vocab_size)
        la, a = ours(p, a, toks, act)
        lb, b = theirs(p, b, toks, act)
        np.testing.assert_array_equal(np.asarray(la, np.float32)[act], np.asarray(lb, np.float32)[act])
    _assert_same(a, b)
    np.testing.assert_array_equal(np.asarray(a["pos"]), np.asarray(pos) + 3 * act)


@pytest.mark.parametrize("rules", [None, SEQ_SHARDED], ids=["scatter", "select"])
@pytest.mark.parametrize("cfg", [DENSE, RING], ids=["full", "ring"])
def test_inactive_rows_keep_every_position(cfg, rules):
    """A parked row's step position is -1, which a scatter or a ring's
    ``pos % cap`` would send to the last position: nothing is written."""
    p = _params(cfg)
    act = np.asarray(MASKS["alternate"], bool)
    before = _arena(cfg, [2, 5, 7, 15, 20, 31])
    after = _step(cfg, rules)(p, before, np.zeros((BATCH, 1), np.int32), act)[1]
    for (name, x), (_, y) in zip(_kv(before), _kv(after)):
        np.testing.assert_array_equal(x[:, ~act], y[:, ~act], err_msg=name)
        assert (x[:, act] != y[:, act]).any(), name


def _written(cfg, pos):
    """The positions of each row one step changes, in every layer."""
    p = _params(cfg)
    before = _arena(cfg, pos)
    after = _step(cfg)(p, before, np.ones((len(pos), 1), np.int32), np.ones(len(pos), bool))[1]
    out = set()
    for (_, x), (_, y) in zip(_kv(before), _kv(after)):
        changed = (x != y).any(axis=(3, 4))  # (L, B, cap)
        out |= {tuple(np.flatnonzero(changed[l, b])) for l in range(x.shape[0]) for b in range(len(pos))}
    return out


def test_full_cache_row_past_capacity_writes_the_last_position():
    assert _written(DENSE, [CAP, CAP + 5]) == {(CAP - 1,)}


@pytest.mark.parametrize("pos,slot", [(3, 3), (8, 0), (13, 5), (31, 7)])
def test_ring_writes_position_modulo_window(pos, slot):
    assert _written(RING, [pos]) == {(slot,)}


def _ref_attn_step(cfg, params, c, x, pos):
    """Attention on one layer's ``(B, cap, KH, D)`` K/V as the step did it
    before the arena was updated in place: a masked write over the whole
    layer (an inactive row, ``pos < 0``, masked out), then the einsum
    attention over the written layer."""
    q, k, v = attn._project_qkv(cfg, params, x, pos[:, None], None)
    cap = c["k"].shape[1]
    slot = pos % cap if cfg.sliding_window else jnp.minimum(pos, cap - 1)
    idx = jnp.arange(cap)
    write = ((idx[None, :] == slot[:, None]) & (pos[:, None] >= 0))[:, :, None, None]
    nk = jnp.where(write, k.astype(c["k"].dtype), c["k"])
    nv = jnp.where(write, v.astype(c["v"].dtype), c["v"])
    valid = idx[None, :] <= slot[:, None]
    if cfg.sliding_window:
        valid = valid | (pos[:, None] >= cap)
    qg = attn._split_gqa(q, cfg.num_kv_heads)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), nk.astype(jnp.float32))
    scores = jnp.where(valid[:, None, None, None, :], scores * (1.0 / cfg.head_dim_**0.5), attn.NEG_INF)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, axis=-1), nv.astype(jnp.float32))
    dt = jnp.dtype(cfg.compute_dtype)
    y = jnp.einsum("bshk,hkd->bsd", out.reshape(q.shape).astype(dt), params["wo"].astype(dt))
    return y, {"k": nk, "v": nv}


def _per_layer_step(cfg, p, cache, toks, act):
    """The step as it was before the arena was updated in place: a scan
    over each layer's parameters and its plain slice of the cache, the new
    slices emitted as the scan's output and stacked by it. No carried
    stack, no write of the code under test."""
    h = M._embed(cfg, p, toks, None)
    pos = jnp.where(act, cache["pos"], -1)
    dt, eps = jnp.dtype(cfg.compute_dtype), cfg.norm_eps

    def body(h, xs):
        params, caches = xs
        new = {}
        for j in range(cfg.period):
            blk, c, kind = params[f"b{j}"], caches[f"b{j}"], cfg.layer_kind(j)
            if kind == "attn":
                y, new[f"b{j}"] = _ref_attn_step(cfg, blk["attn"], c["attn"], M.rms_norm(h, blk["norm"], eps), pos)
            else:
                step = {"mamba": ssm.mamba_apply_step, "mlstm": ssm.mlstm_apply_step,
                        "slstm": ssm.slstm_apply_step}[kind]
                x = M.rms_norm(h, blk["norm"], eps) if kind == "mamba" else h
                y, new[f"b{j}"] = step(cfg, blk[kind], c[kind], x, None)
            new[f"b{j}"] = {kind: new[f"b{j}"]}
            h = h + y
            if "moe" in blk:
                h = h + moe_lib.moe_apply(cfg, blk["moe"], M.rms_norm(h, blk["ffn_norm"], eps), None,
                                          inference=True)[0]
            elif "ffn" in blk:
                h = h + M.mlp_apply(blk["ffn"], M.rms_norm(h, blk["ffn_norm"], eps), dt)
        return h, new

    h, layers = jax.lax.scan(body, h, (p["layers"], cache["layers"]))
    return M._head(cfg, p, h, None), {"pos": cache["pos"] + act.astype(jnp.int32), "layers": layers}


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "xlstm-1.3b"])
def test_hybrid_stack_decodes_bit_equal(arch):
    """Mamba, mLSTM and sLSTM states written whole into their layer of the
    carried stack, attention one position: three steps over two periods
    bit-equal to the layer-by-layer reference."""
    cfg = get_config(arch).reduced(num_layers=2 * get_config(arch).period, d_model=64, vocab_size=64)
    assert cfg.period > 1
    p = _params(cfg)
    act = jnp.asarray(MASKS["alternate"], bool)
    a = b = _arena(cfg, [1, 4, 6, 9, 12, 15])
    ours, theirs = _step(cfg), jax.jit(partial(_per_layer_step, cfg))
    for i in range(3):
        toks = jax.random.randint(jax.random.PRNGKey(20 + i), (BATCH, 1), 0, cfg.vocab_size)
        la, a = ours(p, a, toks, act)
        lb, b = theirs(p, b, toks, act)
        np.testing.assert_array_equal(np.asarray(la, np.float32), np.asarray(lb, np.float32))
    _assert_same(a, b)


def _requests(vocab, n=6, gen=5):
    lens = (6, 9, 12)
    corpus = SyntheticCorpus(vocab, max(lens), 0)
    return [Request(i, corpus.grain_tokens(i, 1)[0][: lens[i % len(lens)]], gen) for i in range(n)]


@pytest.fixture(scope="module")
def loop():
    return ServeLoop(DENSE, RUN, M.init_model(jax.random.PRNGKey(0), DENSE), batch=4,
                     max_len=2 * CAP, mode="arena")


def test_served_session_updates_the_arena_in_place(loop):
    reqs = _requests(DENSE.vocab_size)
    stats = loop.run_requests(reqs)
    assert stats["completed"] == len(reqs)
    assert stats["arena_in_place"] == 1.0


@pytest.mark.parametrize("mode", ["cohort", "serial"])
def test_legacy_modes_touch_no_arena(mode):
    loop = ServeLoop(DENSE, RUN, M.init_model(jax.random.PRNGKey(0), DENSE), batch=2,
                     max_len=2 * CAP, mode=mode)
    stats = loop.run_requests(_requests(DENSE.vocab_size, n=2, gen=2))
    assert stats["arena_in_place"] is None


def _arena_call(loop, program):
    """(arena handed in, lowered program, the call's arguments) for one program."""
    arena = loop._new_arena()
    if program == "decode":
        args = (loop.params, arena, np.zeros((loop.batch, 1), np.int32), np.ones(loop.batch, bool))
        return arena, loop._decode_arena, args, 1
    one = M.init_cache(DENSE, 1, loop.max_len)
    return arena, loop._write_slot, (arena, one, 0), 0


@pytest.mark.parametrize("program", ["decode", "slot_write"])
def test_a_call_consumes_the_arena_it_is_handed(loop, program):
    arena, fn, args, _ = _arena_call(loop, program)
    out = fn(*args)
    new = out[1] if program == "decode" else out
    assert all(x.is_deleted() for x in jax.tree.leaves(arena))
    assert not any(x.is_deleted() for x in jax.tree.leaves(new))


ALIAS = re.compile(r"\{[\d,]*\}: \((\d+), \{\}, (?:may|must)-alias\)")


@pytest.mark.parametrize("program", ["decode", "slot_write"])
def test_program_aliases_every_arena_leaf(loop, program):
    arena, fn, args, at = _arena_call(loop, program)
    text = fn.lower(*args).compile().as_text()
    aliases = re.search(r"input_output_alias=\{ (.*?) \}", text)
    assert aliases, "no input_output_alias in the compiled module"
    first = len(jax.tree.leaves(args[:at]))
    want = set(range(first, first + len(jax.tree.leaves(arena))))
    assert {int(i) for i in ALIAS.findall(aliases.group(1))} == want


@pytest.mark.parametrize(
    "rules,form",
    [
        (None, "scatter"),
        (ShardingRules(mesh_axes=("data",), mesh_shape=(2,)), "scatter"),
        (ShardingRules(mesh_axes=("data", "model"), mesh_shape=(2, 1)), "scatter"),
        (SEQ_SHARDED, "select"),
    ],
    ids=["no-rules", "data-only", "model-of-one", "kv-seq-sharded"],
)
def test_write_form_follows_the_rules(rules, form):
    assert attn.kv_write_form(CAP, rules) == form
    p = _params(DENSE)
    text = jax.jit(partial(M.decode_step, DENSE, RUN, rules=rules)).lower(
        p, M.init_cache(DENSE, BATCH, CAP), np.zeros((BATCH, 1), np.int32)).as_text()
    assert ("stablehlo.scatter" in text) == (form == "scatter")


# A decode step compiled on four host devices, per mesh: the write form the
# rules choose and every all-gather, all-to-all or collective-permute whose
# result has the cache's sequence length (42, which no other dimension of
# the reduced model has and which a model axis of 4 does not divide).
_MESH_PROGRAM = """
import json, re
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.launch.mesh import make_mesh
from repro.launch.steps import named_tree
from repro.models import attention as attn, model as M
from repro.parallel.sharding import rules_from_mesh

cfg = get_config("qwen3-1.7b").reduced(num_layers=3, d_model=64, vocab_size=64)
run = RunConfig(remat="none", attention_impl="xla")
batch, cap = 8, 42
moves = re.compile(r"= \\S*?\\[([\\d,]*)\\]\\S* (all-gather|all-to-all|collective-permute)(?:-start)?\\(")
out = {}
for name, shape, axes in [("data-only", (4,), ("data",)),
                          ("model-not-dividing", (1, 4), ("data", "model")),
                          ("kv-seq-sharded", (2, 2), ("data", "model"))]:
    mesh = make_mesh(shape, axes)
    rules = rules_from_mesh(mesh)
    params = jax.eval_shape(lambda: M.serving_params(cfg, M.init_model(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, batch, cap))
    shard = named_tree(mesh, M.cache_specs(cfg, rules, batch, cap))
    with jax.set_mesh(mesh):
        step = jax.jit(lambda p, c, t: M.decode_step(cfg, run, p, c, t, rules),
                       in_shardings=(None, shard, None), donate_argnums=1)
        text = step.lower(params, cache, jax.ShapeDtypeStruct((batch, 1), jnp.int32)).compile().as_text()
    out[name] = {"form": attn.kv_write_form(cap, rules),
                 "moved": [f"{op} [{dims}]" for dims, op in moves.findall(text)
                           if str(cap) in dims.split(",")]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def on_meshes():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_MESH_PROGRAM)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "mesh,form",
    [("data-only", "scatter"), ("model-not-dividing", "scatter"), ("kv-seq-sharded", "select")],
)
def test_sharded_cache_write_moves_no_cache(on_meshes, mesh, form):
    """On a real mesh, a batch-sharded or replicated cache takes the
    scatter and the sequence-sharded one the select, and neither step
    gathers or reshards a buffer with the cache's sequence length."""
    assert on_meshes[mesh] == {"form": form, "moved": []}
