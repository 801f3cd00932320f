"""Prefill's share of the bf16 peak (``_prefill.py``) where the gap
between tokens is judged: every prefill runs between two decode steps and
stretches the gap of every decoding slot."""

from chipbench.metrics._prefill import read  # noqa: F401

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
