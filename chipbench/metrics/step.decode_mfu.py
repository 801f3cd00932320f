"""Share of the chip's bf16 peak that the arena decode step reaches: the
useful operations of the active rows of the decode calls in the traced
window (``flops.decode_flops``, from the positions the harness recorded
per call) over the device time of the decode programs in the trace."""

from chipbench.flops import decode_flops
from chipbench.trace import device_time

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    if ctx.trace is None or not ctx.decode_calls:
        return None
    seconds, _ = device_time(ctx.trace, "step.decode")
    if seconds <= 0:
        return None
    flops = sum(decode_flops(ctx.config, valid) for valid in ctx.decode_calls)
    return 100 * flops / (seconds * ctx.peak["bf16_flops_per_s"])
