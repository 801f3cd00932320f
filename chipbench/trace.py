"""From a profiler trace to numbers: busy time, idle time in service,
device time per program and per operation.

A trace is read once into plain intervals (seconds on the trace's clock):
the device's operations and programs (lines ``XLA Ops`` and ``XLA
Modules`` of each ``/device:TPU:<n>`` plane), the harness's own host
spans (``chipbench.*`` profiler annotations) and, apart from those, the
program's (``serve.*``, with their arguments). ``chipbench.window`` marks
the traced part of the measured window; everything is clipped to it.
``layers.json`` maps program and operation names to the layers that the
per-layer metrics read.
"""

from __future__ import annotations

import bisect
import glob
import json
import math
import os
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import NamedTuple

LAYERS = Path(__file__).resolve().parent / "layers.json"

Interval = tuple[str, float, float]  # (name, start_s, end_s)


class Span(NamedTuple):
    """A host span the program wrote, with the arguments it gave."""

    name: str
    start: float
    end: float
    args: dict


@dataclass
class Trace:
    ops: dict[str, list[Interval]]  # device plane name -> operations
    modules: dict[str, list[Interval]]  # device plane name -> programs
    spans: list[Interval]  # the harness's host spans
    window: tuple[float, float]
    program: list[Span] = field(default_factory=list)  # the program's serve.* host spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans, program = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9) for e in line.events]
                    (ops if line.name == "XLA Ops" else modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        spans.append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
                    elif e.name.startswith("serve."):
                        program.append(Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9, dict(e.stats)))
    windows = [s for s in spans if s[0] == "chipbench.window"]
    if not windows:
        raise ValueError("the trace holds no chipbench.window span")
    return Trace(ops, modules, spans, (windows[0][1], windows[0][2]), program)


def clip(intervals, lo: float, hi: float) -> list[Interval]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in intervals if b > lo and a < hi]


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of the intervals."""
    out: list[list[float]] = []
    for _, a, b in sorted(intervals, key=lambda x: x[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(cover) -> float:
    return sum(b - a for a, b in cover)


def subtract(cover, minus) -> list[tuple[float, float]]:
    """``cover`` minus ``minus``; both disjoint and sorted."""
    out, j = [], 0
    for a, b in cover:
        while j < len(minus) and minus[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(minus) and minus[k][0] < b:
            if minus[k][0] > cur:
                out.append((cur, minus[k][0]))
            cur = max(cur, minus[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def busy(trace: Trace, plane: str) -> list[tuple[float, float]]:
    """The times in the window when an operation ran on one device."""
    return union(clip(trace.ops.get(plane) or trace.modules[plane], *trace.window))


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the devices traced (0
    where the trace holds no device)."""
    planes = sorted(trace.modules)
    return sum(measure(busy(trace, p)) for p in planes) / len(planes) if planes else 0.0


def program_spans(trace: Trace) -> list[Span]:
    """The program's spans that overlap the window, clipped to it, in order
    of their start."""
    lo, hi = trace.window
    return sorted((s._replace(start=max(s.start, lo), end=min(s.end, hi))
                   for s in trace.program if s.end > lo and s.start < hi), key=lambda s: s.start)


def spans_named(trace: Trace, name: str) -> list[Interval]:
    return clip([s for s in trace.spans if s[0] == name], *trace.window)


def idle_in_service_s(trace: Trace):
    """Seconds in the window when the replica held work (inside a
    ``chipbench.service`` span) and no device ran an operation, averaged
    over devices."""
    service = union(spans_named(trace, "chipbench.service"))
    planes = sorted(trace.modules)
    if not planes:
        return None
    return sum(measure(subtract(service, busy(trace, p))) for p in planes) / len(planes)


def layer_patterns(path: Path = LAYERS) -> dict:
    return json.loads(path.read_text())


def _matching(evs, patterns) -> list[Interval]:
    regs = [re.compile(p) for p in patterns]
    return [e for e in evs if any(r.search(e[0]) for r in regs)]


def device_time(trace: Trace, layer: str, patterns: dict | None = None) -> tuple[float, int]:
    """Seconds and count of the window's device events of ``layer``:
    programs (``XLA Modules``) whose name matches ``programs``, or
    operations (``XLA Ops``) whose name matches ``ops``, counted only
    inside the programs of the layer named by ``within`` when given."""
    table = patterns or layer_patterns()
    entry = table[layer]
    total, count = 0.0, 0
    for plane in trace.modules:
        if "programs" in entry:
            evs = _matching(clip(trace.modules[plane], *trace.window), entry["programs"])
        else:
            evs = _matching(clip(trace.ops.get(plane, []), *trace.window), entry["ops"])
            if "within" in entry:
                outer = union(_matching(trace.modules[plane], table[entry["within"]]["programs"]))
                evs = [e for e in evs if _inside((e[1] + e[2]) / 2, outer)]
        total += sum(b - a for _, a, b in evs)
        count += len(evs)
    return total, count


def _inside(t: float, cover) -> bool:
    i = bisect.bisect_right(cover, (t, math.inf)) - 1
    return i >= 0 and cover[i][0] <= t < cover[i][1]


def _short(name: str) -> str:
    """An operation's name without its layouts, cut to 100 characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:100]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The operations that took most device time (the innermost ones: a
    loop's time is its body's), and the device's idle time in the window
    by the innermost harness span open at the middle of each gap."""
    per_op: dict[str, float] = {}
    if not trace.modules:
        return {"device_ops": [], "idle_gaps": []}
    plane = sorted(trace.modules)[0]
    evs = sorted(clip(trace.ops.get(plane) or trace.modules[plane], *trace.window),
                 key=lambda e: e[1])
    for i, (n, a, b) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][1] < b:
            continue  # a loop or call whose body's operations are listed after it
        per_op[_short(n)] = per_op.get(_short(n), 0.0) + (b - a)
    idle = subtract([trace.window], busy(trace, plane))
    spans = sorted((s for s in trace.spans if s[0] != "chipbench.window"), key=lambda s: s[1])
    per_host: dict[str, float] = {}
    active: list[Interval] = []
    i = 0
    for a, b in idle:  # sorted; spans open at a gap's middle name it
        mid = (a + b) / 2
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] > mid]
        label = min(active, key=lambda s: s[2] - s[1])[0] if active else "outside harness spans"
        per_host[label] = per_host.get(label, 0.0) + (b - a)
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(per_op), "idle_gaps": rank(per_host)}
