"""Prefill's share of the bf16 peak (``_prefill.py``) where time to first
token is judged: a request's first token waits for its own prefill and
for the others admitted in the same tick."""

from chipbench.metrics._prefill import read  # noqa: F401

UNIT = "%"
LAYER = "step programs"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"
