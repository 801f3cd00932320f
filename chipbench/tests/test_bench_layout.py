"""Cells, configurations, mixes and metrics are found by name; the
command refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import spec

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    for d in ("configs", "mixes", "cells", "metrics"):
        shutil.copytree(spec.ROOT / d, root / d, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((root / "configs" / "qwen3-1.7b.json").read_text())
    (root / "configs" / "new-model.json").write_text(json.dumps(dict(cfg, name="new-model")))
    mix = json.loads((root / "mixes" / "chat.json").read_text())
    (root / "mixes" / "bursty.json").write_text(json.dumps(dict(mix, block=8)))
    (root / "cells" / "new-model.bursty.json").write_text(json.dumps(
        {"config": "new-model", "mix": "bursty", "chips": 1, "rate_per_s": 1.0,
         "arena": {"batch": 4, "max_len": 1024}, "limits": {"max_logit_gap": 1.0}, "why": "x"}))
    (root / "metrics" / "sched.new_count.py").write_text(
        'UNIT = "1"\nLAYER = "replica scheduler"\nMOVES = "ttft_p90_ms"\n'
        'SOURCE = "program_counter"\n\n\ndef read(ctx):\n    return len(ctx.records)\n')
    cell = spec.load_cell("new-model.bursty", root)
    assert cell.config["name"] == "new-model" and cell.mix["block"] == 8
    metrics = spec.load_metrics(root)
    assert metrics["sched.new_count"].read(type("C", (), {"records": [1, 2]})()) == 2
    assert set(spec.load_metrics()) < set(metrics)
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root)


def test_benchmark_json_names_what_exists():
    """Every cell, configuration and per-layer metric of BENCHMARK.json has
    its file, with the same chips, unit, layer and moves."""
    metrics = spec.load_metrics()
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.spec["config"] == w["config"] and cell.spec["mix"] == w["traffic"]
        assert cell.spec["chips"] == w["chips"]
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["source"] == c["source"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        mod = metrics[m["name"]]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (m["unit"], m["layer"], m["moves"], m["source"])
        assert m["moves"] in e2e


def test_each_cell_reports_what_benchmark_json_lists_for_it():
    assert spec.reported("end_to_end", "qwen3-1.7b.chat") == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert "ttft_p90_ms" in spec.reported("end_to_end", "qwen3-1.7b.long-prompt")
    chat = spec.reported("per_layer", "internlm2-1.8b.chat")
    assert "step.prefill_mfu.itl" in chat and "sched.queue_wait_p90_ms" not in chat
    for w in BENCH["workloads"]:  # every cell: set-up, another end-to-end metric, a per-layer one
        e2e = spec.reported("end_to_end", w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2 and spec.reported("per_layer", w["name"])


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "qwen3-1.7b.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
