"""p90 of the wait from a request's due time to its admission into a slot
(``Request.submitted``, stamped by ``ServeLoop._admit``), over every
request due in the window; one not admitted by the close waits until it."""

from chipbench.e2e import queue_wait_samples, rank

UNIT = "ms"
LAYER = "replica scheduler"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def read(ctx):
    waits = queue_wait_samples(ctx.records, ctx.close)
    return rank(waits, 90) * 1e3 if waits else None
