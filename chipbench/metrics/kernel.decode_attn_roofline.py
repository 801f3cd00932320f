"""The decode attention kernel's share of its roofline: the least time
its work needs (per call, the larger of operations over peak and bytes
over bandwidth, ``flops.decode_attn_least_s``) over the kernel's device
time in the trace. The work is the keys and values of each active row's
valid positions, with its query and output, counted from the positions
the harness recorded per call, never from the arena's capacity: every
implementation must move at least that, so skipping empty blocks cannot
read over 100%."""

from chipbench.flops import decode_attn_least_s
from chipbench.trace import device_time

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    if ctx.trace is None or not ctx.decode_calls:
        return None
    seconds, count = device_time(ctx.trace, "kernel.decode_attention")
    if seconds <= 0 or count == 0:
        return None
    least = sum(decode_attn_least_s(ctx.config, valid, ctx.peak) for valid in ctx.decode_calls)
    return 100 * least / seconds
