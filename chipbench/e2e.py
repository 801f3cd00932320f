"""End-to-end metrics of one window, from its records (``driver.Record``).

A request that is still waiting at the close counts as waiting until the
close, so a stall raises a tail instead of hiding it. Percentiles are
nearest-rank: the value at rank ``ceil(p/100 · n)`` of the sorted sample.
"""

from __future__ import annotations

import math


def rank(values: list[float], p: float) -> float:
    if not values:
        raise ValueError("no samples")
    v = sorted(values)
    return v[max(1, math.ceil(p / 100 * len(v))) - 1]


def in_window(rec, close: float) -> list[float]:
    return [t for t in rec.stamps if t <= close]


def tokens_per_s(records, close: float) -> float:
    """Output tokens stamped in the window over its length."""
    return sum(len(in_window(r, close)) for r in records) / close


def ttft_samples(records, close: float) -> list[float]:
    """First-token stamp minus due time, for every request due in the
    window; a request without a first token by the close counts to it."""
    out = []
    for rec in records:
        if rec.due >= close:
            continue
        first = rec.req.first_token
        out.append((first if 0 <= first <= close else close) - rec.due)
    return out


def itl_samples(records, close: float) -> list[float]:
    """Every gap between consecutive tokens of one request that ends in
    the window, plus the open gap to the close of each request still
    decoding then."""
    out = []
    for rec in records:
        s = in_window(rec, close)
        out += [b - a for a, b in zip(s, s[1:])]
        if s and len(s) < rec.req.max_new:
            out.append(close - s[-1])
    return out


def queue_wait_samples(records, close: float) -> list[float]:
    """Admission (``Request.submitted``) minus due time; a request not
    admitted by the close counts to it."""
    out = []
    for rec in records:
        if rec.due >= close:
            continue
        r = rec.req
        admitted = r.first_token >= 0 and r.submitted <= close
        out.append((r.submitted if admitted else close) - rec.due)
    return out


def end_to_end(records, close: float) -> dict:
    """``{name: (value, unit, n_samples)}`` of the cell's end-to-end
    metrics other than ``setup_s``."""
    ttft, itl = ttft_samples(records, close), itl_samples(records, close)
    return {
        "tokens_per_s": (tokens_per_s(records, close), "tokens/s",
                         sum(len(in_window(r, close)) for r in records)),
        "ttft_p90_ms": (rank(ttft, 90) * 1e3, "ms", len(ttft)),
        "itl_p95_ms": (rank(itl, 95) * 1e3, "ms", len(itl)),
    }
