"""A benchmark root in a temporary directory, at smoke size, for tests:
the real metric readers, a configuration cut from ``qwen3-1.7b`` (two
layers of width 64, as ``ModelConfig.reduced`` cuts it), mixes shaped as
``chat`` and ``long-prompt`` with short lengths, and one cell per mix."""

import json
import shutil
from pathlib import Path

from chipbench import spec

SMOKE = dict(name="qwen3-1.7b-smoke", hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=256)


def make_root(tmp: Path, limit: float = 1.0) -> Path:
    root = tmp / "bench"
    shutil.copytree(spec.ROOT / "metrics", root / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "mixes", "cells"):
        (root / d).mkdir(parents=True)
    cfg = json.loads((spec.ROOT / "configs" / "qwen3-1.7b.json").read_text())
    cfg.update(SMOKE)
    (root / "configs" / "qwen3-1.7b-smoke.json").write_text(json.dumps(cfg))
    for name, prompt in (("chat", {"min": 16, "max": 64, "round_up": 16}),
                         ("long-prompt", {"min": 32, "max": 96, "step": 32})):
        mix = json.loads((spec.ROOT / "mixes" / f"{name}.json").read_text())
        mix["prompt"].update(prompt)
        mix["output"].update(min=4, max=24, median=10)
        mix["sample"] = {"min_tokens": 120, "max_requests": 24}
        mix["trace_seconds"] = 1
        (root / "mixes" / f"{name}.json").write_text(json.dumps(mix))
        cell = {"config": "qwen3-1.7b-smoke", "mix": name, "chips": 1,
                "arena": {"batch": 4, "max_len": 256}, "limits": {"max_logit_gap": limit},
                "why": "smoke"}
        cell.update({"rate_per_s": 8} if name == "chat" else {"clients": 4})
        (root / "cells" / f"smoke.{name}.json").write_text(json.dumps(cell))
    return root


def cpu_chip(jax, chips):
    """Stands in for run.require_chip on the CPU, with a device kind the
    peaks table knows."""
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
