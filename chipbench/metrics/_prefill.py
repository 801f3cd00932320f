"""Share of the chip's bf16 peak that the prefill programs reach: the
useful operations of the prompts prefilled in the traced window (counted
from their lengths by ``flops.prefill_flops``) over the device time of
the prefill programs in the trace. Two metrics read it, one per
end-to-end metric that prefill moves."""

from chipbench.flops import prefill_flops
from chipbench.trace import device_time


def read(ctx):
    if ctx.trace is None or not ctx.prefill_calls:
        return None
    seconds, _ = device_time(ctx.trace, "step.prefill")
    if seconds <= 0:
        return None
    flops = sum(prefill_flops(ctx.config, n) for n in ctx.prefill_calls)
    return 100 * flops / (seconds * ctx.peak["bf16_flops_per_s"])
