"""Architectures are modules found by ``model_type``: the dense ones make
the weights and gaps they made before they were modules, a new one is
added with new files only, weights are made in the configuration's
``param_dtype``, and an unknown type is an error that names the known."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench.system as system
from chipbench import reference, run, spec, trace
from chipbench.tests import smoke
from chipbench.weights import make_weights

SMOKE = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=256)
SEED = 2**33 + 17
LIMIT = 0.02  # the smoke limit of test_bench_faults.py

# Recorded from the weight maker and the reference as they were when both
# were written for the dense decoder alone: per leaf, the float64 sum of
# the float32 values and the elements first, at the middle and last of
# its flattened array; per configuration, the widest gap, the sum of the
# gaps and the float8 control's widest gap on a fixed sample.
PARENT = {
    "qwen3-1.7b": ({
        "embed": (0.27584818005232137, -0.01679239049553871, -0.004111278336495161, 0.02444324642419815),
        "final_norm": (-0.6080952193588018, 0.12872087955474854, -0.05661730095744133, 0.1290009617805481),
        "layers/b0/attn/k_norm": (-0.4754286491079256, -0.1442541480064392, -0.0017184930620715022, 0.042501647025346756),
        "layers/b0/attn/q_norm": (-0.31771015701815486, 0.029950890690088272, 0.15050210058689117, 0.09004873037338257),
        "layers/b0/attn/wk": (-5.550866243196651, -0.013745340518653393, -0.060231100767850876, 0.1511237770318985),
        "layers/b0/attn/wo": (3.8787444909135047, -0.12004942446947098, 0.03430036082863808, -0.09187417477369308),
        "layers/b0/attn/wq": (3.4702231400005985, 0.09899003803730011, 0.08127261698246002, 0.140641450881958),
        "layers/b0/attn/wv": (-0.2923759784698632, 0.2806102931499481, 0.035845059901475906, 0.136971578001976),
        "layers/b0/ffn/down": (-0.21739074220408838, 0.1161421537399292, -0.1328355222940445, -0.13991087675094604),
        "layers/b0/ffn/gate": (20.288729814277758, -0.13680574297904968, 0.0012841449351981282, -0.2917267680168152),
        "layers/b0/ffn/up": (-8.16937725878961, -0.010897384025156498, -0.2535287141799927, 0.11105639487504959),
        "layers/b0/ffn_norm": (-2.4060226993169636, -0.010783005505800247, -0.15261980891227722, -0.049979738891124725),
        "layers/b0/norm": (-1.1470049534982536, -0.04843619838356972, -0.02283359318971634, -0.10579562187194824),
    }, (0.9081434607505798, 121.22296325862408, 0.08834394812583923)),
    "internlm2-1.8b": ({
        "embed": (0.27584818005232137, -0.01679239049553871, -0.004111278336495161, 0.02444324642419815),
        "final_norm": (-0.6080952193588018, 0.12872087955474854, -0.05661730095744133, 0.1290009617805481),
        "layers/b0/attn/wk": (-14.94687308936409, -0.180317685008049, -0.0723080039024353, -0.0879698097705841),
        "layers/b0/attn/wo": (3.5643857757331716, 0.03743860870599747, 0.10893213003873825, 0.08530260622501373),
        "layers/b0/attn/wq": (-6.079126693759463, -0.013745340518653393, -0.16930511593818665, -0.014708248898386955),
        "layers/b0/attn/wv": (-2.376194531270812, -0.12004942446947098, -0.0017220317386090755, -0.1672457456588745),
        "layers/b0/ffn/down": (8.049839127809719, 0.06999652087688446, -0.1435239464044571, 0.06093360483646393),
        "layers/b0/ffn/gate": (0.7280534067758708, 0.2806102931499481, 0.09620443731546402, 0.19115839898586273),
        "layers/b0/ffn/up": (-0.30743616152221875, 0.16424982249736786, -0.18785782158374786, -0.1978638768196106),
        "layers/b0/ffn_norm": (0.5062257095705718, -0.10944459587335587, 0.06649153679609299, -0.14720824360847473),
        "layers/b0/norm": (-0.08118508115876466, -0.008717907592654228, -0.07919217646121979, -0.05777100473642349),
        "lm_head": (-2.483854001553027, -0.013478755950927734, 0.14680194854736328, 0.04249492660164833),
    }, (5.601507186889648, 707.4282448291779, 0.7366793155670166)),
}


def _smoke_config(name: str) -> dict:
    """The configuration file cut to smoke widths; qwen3 keeps its stated
    head_dim, internlm2 derives its own."""
    c = spec.load_cell(f"{name}.chat").config
    return dict(c, **SMOKE, **({"head_dim": 16} if "head_dim" in c else {}))


def _leaves(w) -> dict:
    return {"/".join(k.key for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(w)[0]}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_weights_match_the_dense_harness(name):
    leaves = _leaves(make_weights(_smoke_config(name), SEED))
    want = PARENT[name][0]
    assert sorted(leaves) == sorted(want)
    for path, v in leaves.items():
        flat = v.reshape(-1)
        assert v.dtype == np.float32
        got = (float(v.astype(np.float64).sum()), float(flat[0]), float(flat[flat.size // 2]),
               float(flat[-1]))
        assert got == want[path], path


@pytest.mark.parametrize("name", sorted(PARENT))
def test_reference_gaps_match_the_dense_harness(name):
    c = _smoke_config(name)
    w = make_weights(c, SEED)
    rng = np.random.default_rng(3)
    tokens, targets = (rng.integers(0, 256, 256).astype(np.int32) for _ in range(2))
    key = json.dumps(c, sort_keys=True)
    gaps = np.asarray(reference.gap_fn(key, False)(w, tokens, targets))
    control = np.asarray(reference.gap_fn(key, True)(w, tokens, targets)[1])
    assert (float(gaps.max()), float(gaps.astype(np.float64).sum()), float(control.max())) == PARENT[name][1]


# A new architecture: the dense decoder whose norm gains are drawn three
# times as wide, written as a module of its own beside a configuration,
# a cell and a metric that reads the program's counters and spans.
NEW_ARCH = '''"""The dense decoder with wider norm gains."""

from chipbench.arch import _dense
from chipbench.arch._dense import *  # noqa: F401,F403


def layout(c):
    wide = lambda std: 3 * std if std == _dense.NORM_STD else std
    return {p: (shape, wide(std), axes) for p, (shape, std, axes) in _dense.layout(c).items()}
'''

NEW_METRIC = '''UNIT = "rows"
LAYER = "replica scheduler"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def read(ctx):
    rows = [s.args["rows"] for s in ctx.spans if s.name == "serve.decode"]
    if not rows or not ctx.stats["decode_calls"]:
        return None
    return sum(rows) / len(rows)
'''


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(system, "enable_compile_cache", lambda: None)
    saved = jax.config.jax_persistent_cache_min_compile_time_secs
    yield smoke.make_root(tmp_path, limit=LIMIT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved)


def _add_cell(root, name: str, **changes) -> str:
    """A configuration ``name``: the smoke one with ``changes``, and its
    chat cell, whose name is returned."""
    cfg = json.loads((root / "configs" / "qwen3-1.7b-smoke.json").read_text())
    (root / "configs" / f"{name}.json").write_text(json.dumps(dict(cfg, name=name, **changes)))
    cell = json.loads((root / "cells" / "smoke.chat.json").read_text())
    (root / "cells" / f"{name}.chat.json").write_text(json.dumps(dict(cell, config=name)))
    return f"{name}.chat"


def test_new_architecture_with_new_files_only(root):
    (root / "arch").mkdir()
    (root / "arch" / "wide_gain.py").write_text(NEW_ARCH)
    (root / "metrics" / "sched.rows_per_decode.py").write_text(NEW_METRIC)
    cell = _add_cell(root, "wide-gain-smoke", model_type="wide_gain")
    c = spec.load_cell(cell, root).config
    gains = _leaves(make_weights(c, 5))["layers/b0/norm"]
    assert 0.2 < float(gains.std()) < 0.45  # three times the dense 0.1
    res = run.main(["--workload", cell, "--seed", str(2**31 + 9), "--seconds", "4", "--trace", "1"],
                   root=root, chip_check=smoke.cpu_chip)
    assert res["correct"] is True, res["check"]
    rows = res["metrics"]["sched.rows_per_decode"]
    assert rows["unit"] == "rows" and 0 < rows["value"] <= 4  # the arena's four slots


def test_bfloat16_param_dtype(root):
    cell = _add_cell(root, "bf16-smoke", param_dtype="bfloat16")
    c = spec.load_cell(cell, root).config
    assert {x.dtype for x in jax.tree.leaves(make_weights(c, 5))} == {jnp.dtype(jnp.bfloat16)}
    res = run.main(["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "4", "--trace", "0"],
                   root=root, chip_check=smoke.cpu_chip)
    assert res["correct"] is True, res["check"]
    assert res["check"]["max_logit_gap_at_most"]["value"] <= LIMIT


def test_unknown_model_type_names_the_known(root):
    cell = _add_cell(root, "odd-smoke", model_type="no_such_type")
    with pytest.raises(KeyError, match=r"no_such_type.*\['internlm2', 'qwen3'\]"):
        spec.load_cell(cell, root)


def test_program_spans_are_clipped_to_the_window():
    t = trace.Trace(ops={}, modules={}, spans=[("chipbench.window", 1.0, 3.0)], window=(1.0, 3.0),
                    program=[trace.Span("serve.decode", 2.5, 3.5, {"rows": 2}),
                             trace.Span("serve.admit", 0.5, 1.5, {"tokens": 64}),
                             trace.Span("serve.tick", 3.5, 4.0, {})])
    assert trace.program_spans(t) == [trace.Span("serve.admit", 1.0, 1.5, {"tokens": 64}),
                                      trace.Span("serve.decode", 2.5, 3.0, {"rows": 2})]
