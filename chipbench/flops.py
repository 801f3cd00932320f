"""Operations and bytes the served decoder needs, counted from shapes.

"Useful" work only: the products the algorithm needs for the tokens
served, not what an implementation pads or recomputes. A matrix product
of ``m × k`` by ``k × n`` is ``2·m·k·n`` operations. Causal prefill
attention counts the ``S·(S+1)/2`` query-key pairs at or below the
diagonal; the prefill's head runs for the last position only, as the
program's ``prefill`` does. Sizes come from the configuration file.
"""

from __future__ import annotations

import json
from pathlib import Path

from chipbench.weights import dims

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(c: dict) -> int:
    z = dims(c)
    attn = z["d"] * z["hd"] * (2 * z["H"] + 2 * z["KH"])
    return attn + 3 * z["d"] * z["ff"]


def head_params(c: dict) -> int:
    z = dims(c)
    return z["d"] * z["V"]


def kv_bytes_per_token(c: dict) -> int:
    z = dims(c)
    return 2 * z["L"] * z["KH"] * z["hd"] * DTYPE_BYTES[c["compute_dtype"]]


def prefill_flops(c: dict, s: int) -> int:
    """One prompt of ``s`` tokens: projections and FFN for every token,
    causal attention, the head for the last position."""
    z = dims(c)
    attn = 4 * z["H"] * z["hd"] * (s * (s + 1) // 2)
    return z["L"] * (2 * layer_matmul_params(c) * s + attn) + 2 * head_params(c)


def decode_flops(c: dict, valid: list[int]) -> int:
    """One decode step over active rows whose caches hold ``valid[i]``
    positions each (the new token's included)."""
    n_layers = dims(c)["L"]
    return (len(valid) * 2 * (n_layers * layer_matmul_params(c) + head_params(c))
            + n_layers * decode_attn_flops_per_layer(c, valid))


def decode_attn_flops_per_layer(c: dict, valid: list[int]) -> int:
    z = dims(c)
    return 4 * z["H"] * z["hd"] * sum(valid)


def decode_attn_bytes_per_layer(c: dict, valid: list[int]) -> int:
    """The keys and values of each active row's valid positions, its query
    and its output, in the compute dtype: what any implementation of one
    layer's decode attention must move."""
    z = dims(c)
    e = DTYPE_BYTES[c["compute_dtype"]]
    return e * (2 * z["KH"] * z["hd"] * sum(valid) + 2 * z["H"] * z["hd"] * len(valid))


def decode_attn_least_s(c: dict, valid: list[int], peak: dict) -> float:
    """Least time of one decode step's attention, all layers: the larger of
    operations over peak and bytes over bandwidth."""
    flops = decode_attn_flops_per_layer(c, valid)
    nbytes = decode_attn_bytes_per_layer(c, valid)
    return dims(c)["L"] * max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
