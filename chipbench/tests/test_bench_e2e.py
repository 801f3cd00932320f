"""Tail arithmetic of the window, checked against hand counts, with the
censoring at the close."""

import numpy as np
import pytest

from chipbench import e2e
from chipbench.driver import Record
from chipbench.system import Request


def rec(rid, due, stamps, max_new, submitted=None, finished=-1.0):
    r = Request(rid, np.zeros(8, np.int32), max_new)
    r.arrived = due
    r.first_token = stamps[0] if stamps else -1.0
    r.submitted = submitted if submitted is not None else (stamps[0] - 0.05 if stamps else 0.0)
    r.tokens = [0] * len(stamps)
    r.finished = finished
    return Record(r, due, due, list(stamps))


CLOSE = 10.0
RECORDS = [
    rec(0, 0.0, [0.5, 1.0, 1.5], 3, finished=1.5),  # done: ttft 0.5, gaps 0.5 0.5
    rec(1, 2.0, [2.2, 2.4, 9.0], 5),  # still decoding: gaps .2, 6.6, open gap 1.0
    rec(2, 9.5, [], 4),  # never admitted: ttft censored to 0.5, queue wait 0.5
    rec(3, 8.0, [9.9, 10.4], 2, finished=10.4),  # second token after the close: open gap 0.1
    rec(4, 10.5, [], 4),  # due after the close: not counted
]


def test_rank_is_nearest_rank():
    v = list(range(1, 101))
    assert e2e.rank(v, 90) == 90 and e2e.rank(v, 95) == 95 and e2e.rank([7.0], 90) == 7.0
    assert e2e.rank([1, 2, 3], 90) == 3 and e2e.rank([4, 1, 3, 2], 50) == 2
    with pytest.raises(ValueError):
        e2e.rank([], 90)


def test_ttft_censors_at_the_close():
    assert sorted(e2e.ttft_samples(RECORDS, CLOSE)) == pytest.approx([0.2, 0.5, 0.5, 1.9])


def test_itl_counts_open_gaps():
    assert sorted(e2e.itl_samples(RECORDS, CLOSE)) == pytest.approx([0.1, 0.2, 0.5, 0.5, 1.0, 6.6])


def test_tokens_and_queue_wait():
    assert e2e.tokens_per_s(RECORDS, CLOSE) == pytest.approx(7 / 10)  # 3 + 3 + 0 + 1
    assert sorted(e2e.queue_wait_samples(RECORDS, CLOSE)) == pytest.approx([0.15, 0.45, 0.5, 1.85])
    out = e2e.end_to_end(RECORDS, CLOSE)
    assert out["ttft_p90_ms"][0] == pytest.approx(1900) and out["ttft_p90_ms"][2] == 4
    assert out["itl_p95_ms"][0] == pytest.approx(6600) and out["itl_p95_ms"][2] == 6
