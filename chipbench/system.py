"""The system under test: ``repro.launch.serve.ServeLoop`` in arena mode.

The only module of the benchmark that imports the program. It turns a
configuration file into the program's ``ModelConfig`` (by the
configuration's architecture module, which imports it from here) and
builds the replica the window drives: admission ``admit_all``, greedy
decoding fused into the arena step, decode attention ``auto`` (the Pallas
kernel on a TPU), prefill attention ``xla`` as ``repro.launch.serve.main``
sets it.
"""

from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from chipbench import spec  # noqa: E402
from repro.configs.base import ModelConfig, RunConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import Request, ServeLoop  # noqa: E402
from repro.models.model import serving_params  # noqa: E402

__all__ = ["Request", "ServeLoop", "build_loop", "enable_compile_cache", "model_config", "serving_params"]


def model_config(c: dict) -> "ModelConfig":
    """The program's ``ModelConfig`` for a configuration file, as its
    architecture module (``arch/<model_type>.py``) states it."""
    cfg = spec.arch(c).model_config(c)
    cfg.validate()
    return cfg


def build_loop(c: dict, params, batch: int, max_len: int) -> "ServeLoop":
    run = RunConfig(remat="none", attention_impl="xla")
    return ServeLoop(model_config(c), run, params, batch=batch, max_len=max_len,
                     admission="admit_all", mode="arena")
