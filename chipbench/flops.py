"""Operations and bytes the served decoder needs, counted from shapes.

Each count is the configuration's architecture module's (``arch/``), which
says what it counts; here are the dispatch, the table of peaks and the
least time of the decode attention that the kernel's roofline divides by.
"""

from __future__ import annotations

import json
from pathlib import Path

from chipbench import spec

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def kv_bytes_per_token(c: dict) -> int:
    """The cache one token keeps, all layers, in the compute dtype."""
    return spec.arch(c).kv_bytes_per_token(c)


def prefill_flops(c: dict, s: int) -> int:
    """One prompt of ``s`` tokens, through the head at its last position."""
    return spec.arch(c).prefill_flops(c, s)


def decode_flops(c: dict, valid: list[int]) -> int:
    """One decode step over active rows whose caches hold ``valid[i]``
    positions each (the new token's included)."""
    return spec.arch(c).decode_flops(c, valid)


def decode_attn_flops_per_layer(c: dict, valid: list[int]) -> int:
    return spec.arch(c).decode_attn_flops_per_layer(c, valid)


def decode_attn_bytes_per_layer(c: dict, valid: list[int]) -> int:
    """What any implementation of one layer's decode attention must move
    over rows holding ``valid[i]`` positions each."""
    return spec.arch(c).decode_attn_bytes_per_layer(c, valid)


def decode_attn_least_s(c: dict, valid: list[int], peak: dict) -> float:
    """Least time of one decode step's attention, all layers: the larger of
    operations over peak and bytes over bandwidth."""
    flops = decode_attn_flops_per_layer(c, valid)
    nbytes = decode_attn_bytes_per_layer(c, valid)
    return c["num_hidden_layers"] * max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
