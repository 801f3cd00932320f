"""The system under test: ``repro.launch.serve.ServeLoop`` in arena mode.

The only module of the benchmark that imports the program. It turns a
configuration file into the program's ``ModelConfig`` and builds the
replica the window drives: admission ``admit_all``, greedy decoding fused
into the arena step, decode attention ``auto`` (the Pallas kernel on a
TPU), prefill attention ``xla`` as ``repro.launch.serve.main`` sets it.
"""

from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.configs.base import ModelConfig, RunConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import Request, ServeLoop  # noqa: E402

__all__ = ["Request", "ServeLoop", "build_loop", "enable_compile_cache", "model_config"]


def model_config(c: dict) -> "ModelConfig":
    """The program's ``ModelConfig`` for a configuration file."""
    cfg = ModelConfig(
        name=c["name"],
        family="dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", 0),
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        qk_norm=c["qk_norm"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"],
        source=c["source"],
    )
    cfg.validate()
    return cfg


def build_loop(c: dict, params, batch: int, max_len: int) -> "ServeLoop":
    run = RunConfig(remat="none", attention_impl="xla")
    return ServeLoop(model_config(c), run, params, batch=batch, max_len=max_len,
                     admission="admit_all", mode="arena")
