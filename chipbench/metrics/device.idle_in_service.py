"""Share of the traced window in which the replica held work (the
harness's ``chipbench.service`` spans: a tick with a decoding slot or a
queued request) and the device ran no operation: the host's part of
serving, such as the per-step token round trip."""

from chipbench.trace import idle_in_service_s

UNIT = "%"
LAYER = "device"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    idle = idle_in_service_s(ctx.trace)
    return None if idle is None else 100 * idle / ctx.trace.window_s
