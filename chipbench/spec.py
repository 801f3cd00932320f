"""Find a cell, its configuration, its mix, its architecture and the
per-layer metrics by name.

A later change adds a cell, a configuration, a mix, an architecture or a
metric by adding a file here; nothing in the harness names one.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Cell:
    name: str
    spec: dict  # cells/<name>.json
    config: dict  # configs/<spec["config"]>.json
    mix: dict  # mixes/<spec["mix"]>.json


def _read(root: Path, kind: str, name: str) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (root / kind).glob("*.json"))
        raise KeyError(f"no {kind[:-1]} {name!r} under {root / kind}; known: {known}")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell and what it names. ``root``'s ``arch/`` is searched first
    for the architecture of this and every later configuration."""
    global _arch_root
    spec = _read(root, "cells", name)
    config = _read(root, "configs", spec["config"])
    _arch_root = root
    arch(config)
    return Cell(name, spec, config, _read(root, "mixes", spec["mix"]))


ARCH_FUNCTIONS = ("layout", "model_config", "hidden", "head", "param_count", "kv_bytes_per_token",
                  "prefill_flops", "decode_flops", "decode_attn_flops_per_layer",
                  "decode_attn_bytes_per_layer")

_arch_root = ROOT  # the root of the last cell loaded


@functools.lru_cache(maxsize=None)
def _arch_module(path: Path) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location("chipbench_arch_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    for attr in ARCH_FUNCTIONS:
        if not hasattr(mod, attr):
            raise AttributeError(f"architecture {path.stem} lacks {attr}")
    return mod


def arch(c: dict) -> ModuleType:
    """The module ``arch/<model_type>.py`` of configuration ``c``, with
    ``ARCH_FUNCTIONS``: under the root of the last cell loaded, else under
    the benchmark's own ``arch/``. An unknown type is an error that names
    the known ones."""
    dirs = tuple(dict.fromkeys((_arch_root / "arch", ROOT / "arch")))
    for d in dirs:
        if (d / f"{c['model_type']}.py").is_file():
            return _arch_module(d / f"{c['model_type']}.py")
    known = sorted({p.stem for d in dirs for p in d.glob("[!_]*.py")})
    raise KeyError(f"no architecture for model_type {c['model_type']!r}; known: {known}")


def reported(kind: str, cell: str, root: Path = ROOT):
    """The names of ``kind`` (``end_to_end`` or ``per_layer``) metrics that
    ``BENCHMARK.json`` beside ``root`` lists for ``cell``: those without a
    ``workloads`` key and those whose key names it. None where there is no
    ``BENCHMARK.json`` (a root made for a test): every metric is reported."""
    path = root.parent / "BENCHMARK.json"
    if not path.is_file():
        return None
    return {m["name"] for m in json.loads(path.read_text())[kind]
            if cell in m.get("workloads", [cell])}


def load_metrics(root: Path = ROOT) -> dict[str, ModuleType]:
    """Every per-layer metric reader under ``metrics/``, keyed by its name
    (the file's stem). Each module has ``UNIT``, ``LAYER``, ``MOVES``,
    ``SOURCE`` and ``read(ctx) -> float | None``."""
    out = {}
    for path in sorted((root / "metrics").glob("*.py")):
        if path.stem.startswith("_"):
            continue
        mod_name = "chipbench_metric_" + path.stem.replace(".", "_").replace("-", "_")
        mod_spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        for attr in ("UNIT", "LAYER", "MOVES", "SOURCE", "read"):
            if not hasattr(mod, attr):
                raise AttributeError(f"metric {path.stem} lacks {attr}")
        out[path.stem] = mod
    return out
