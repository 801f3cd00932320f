"""The measured window: drives ``ServeLoop``'s session API on the host clock.

The driver only offers load and writes down what it sees. It enqueues
each request when it is due (open loop) or when a client's last request
finished (closed loop), stamping ``arrived`` with the due time, so a late
generator or a stalled tick counts against the system. It calls
``tick()`` whenever the replica holds work and sleeps otherwise, and
after every tick stamps each new token of every request. ``ServeLoop``
does the scheduling, prefill, slot writes and decoding.

Times are seconds on ``time.perf_counter`` from the window's opening.
The first token's stamp is the program's own ``first_token`` (taken after
the prefill's host sync); later tokens are stamped when the tick that
made them returns.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from chipbench.system import Request


@dataclass
class Record:
    req: Request
    due: float
    sent: float  # when the driver enqueued it
    stamps: list[float] = field(default_factory=list)


def null_span(name: str):
    return contextlib.nullcontext()


def drive(loop, traffic, seconds: float, *, clients: Optional[int] = None,
          span: Callable = null_span, at: Optional[tuple[float, Callable]] = None) -> list[Record]:
    """Run one window of ``seconds`` and return a record per request sent.

    ``clients`` makes the loop closed; ``span(name)`` opens a host span
    (a profiler annotation in a traced run); ``at = (t, fn)`` calls ``fn``
    once the window reaches ``t`` seconds."""
    records: list[Record] = []
    live: list[Record] = []
    nxt = 0

    def send(due: float) -> None:
        nonlocal nxt
        p = traffic[nxt]
        nxt += 1
        r = Request(p.rid, p.prompt, p.max_new)
        r.arrived = due
        rec = Record(r, due, loop.now())
        records.append(rec)
        live.append(rec)
        loop.enqueue(r)

    loop.start([], t0=time.perf_counter())
    if clients:
        for _ in range(clients):
            send(0.0)
    while True:
        now = loop.now()
        if now >= seconds:
            break
        if at is not None and now >= at[0]:
            at[1]()
            at = None
        if not clients:
            while nxt < len(traffic) and traffic.dues[nxt] <= now:
                send(float(traffic.dues[nxt]))
        if loop.idle:
            wake = float(traffic.dues[nxt]) if not clients and nxt < len(traffic) else seconds
            if at is not None:
                wake = min(wake, at[0])
            with span("chipbench.wait"):
                time.sleep(max(0.0, min(wake, seconds) - loop.now()))
            continue
        with span("chipbench.service"):
            with span("chipbench.tick"):
                loop.tick()
            t = loop.now()
            still, freed = [], []
            for rec in live:
                r = rec.req
                if len(r.tokens) > len(rec.stamps):
                    if not rec.stamps:
                        rec.stamps.append(r.first_token)
                    rec.stamps.extend([t] * (len(r.tokens) - len(rec.stamps)))
                if r.finished >= 0:
                    freed.append(r.finished)
                else:
                    still.append(rec)
            live[:] = still
            if clients:
                for due in freed:  # the client sends its next request at once
                    send(due)
    return records
