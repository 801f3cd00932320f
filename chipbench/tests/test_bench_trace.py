"""The reduction from a trace to numbers: on hand-made intervals, and on
0.3 s of a trace recorded on the chip (``data/chat_trace_slice.json.gz``:
the device's operations and programs and the harness's spans of a
``qwen3-1.7b.chat`` run with ``--trace 1`` on a TPU v5 lite), against a
brute-force count on a grid of microseconds."""

import gzip
import json
from pathlib import Path

import numpy as np

import pytest

from chipbench import trace

SLICE = Path(__file__).parent / "data" / "chat_trace_slice.json.gz"

DEV = "/device:TPU:0"


def _trace():
    # device: ops in [0,1] and [2,3], a program around each; host: the
    # window [0,4], a service span [0.5,3.5] with a tick inside, a wait after
    return trace.Trace(
        ops={DEV: [("fusion.1", 0.0, 1.0), ("decode_kernel", 2.0, 2.5), ("fusion.2", 2.5, 3.0)]},
        modules={DEV: [("jit__arena_decode", 0.0, 1.0), ("jit__arena_decode", 2.0, 3.0),
                       ("jit_<lambda>", 5.0, 6.0)]},
        spans=[("chipbench.window", 0.0, 4.0), ("chipbench.service", 0.5, 3.5),
               ("chipbench.tick", 0.6, 3.4), ("chipbench.wait", 3.5, 4.0)],
        window=(0.0, 4.0),
    )


def test_union_subtract_measure():
    cover = trace.union([("a", 0, 2), ("b", 1, 3), ("c", 5, 6)])
    assert cover == [(0, 3), (5, 6)] and trace.measure(cover) == 4
    assert trace.subtract([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == [(0, 1), (2, 4), (5, 9)]
    assert trace.subtract([(0, 1), (3, 4)], [(0.5, 3.5)]) == [(0, 0.5), (3.5, 4)]
    assert trace.clip([("x", -1, 1), ("y", 3, 9), ("z", 9, 10)], 0, 4) == [("x", 0, 1), ("y", 3, 4)]


def test_busy_idle_and_device_time():
    t = _trace()
    assert trace.busy_s(t) == pytest.approx(2.0)
    # service [0.5,3.5] minus busy [0,1]∪[2,3] leaves [1,2] and [3,3.5]
    assert trace.idle_in_service_s(t) == pytest.approx(1.5)
    pats = {"step.decode": {"programs": ["^jit__arena_decode"]},
            "kernel": {"ops": ["decode_kernel"], "within": "step.decode"},
            "pre": {"programs": ["lambda"]}}
    assert trace.device_time(t, "step.decode", pats) == (pytest.approx(2.0), 2)
    assert trace.device_time(t, "kernel", pats) == (pytest.approx(0.5), 1)
    assert trace.device_time(t, "pre", pats) == (0.0, 0)  # outside the window


def test_breakdown_names_ops_and_idle_by_host_span():
    b = trace.breakdown(_trace())
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(1.0)]
    # idle gaps [1,2] and [3,4]: each goes whole to the innermost span
    # open at its middle, the tick at 1.5 and the wait at 3.5
    assert dict((k, pytest.approx(v)) for k, v in b["idle_gaps"]) == {
        "chipbench.tick": 1.0, "chipbench.wait": 1.0}


def _slice():
    d = json.loads(gzip.decompress(SLICE.read_bytes()))
    tup = lambda evs: [tuple(e) for e in evs]
    return trace.Trace({k: tup(v) for k, v in d["ops"].items()},
                       {k: tup(v) for k, v in d["modules"].items()},
                       tup(d["spans"]), tuple(d["window"]))


def _grid(t, intervals, step=1e-6):
    """A mask over the window, one point per microsecond, true inside any interval."""
    n = int(round(t.window_s / step))
    mask = np.zeros(n, bool)
    for _, a, b in intervals:
        mask[int((a - t.window[0]) / step):int(np.ceil((b - t.window[0]) / step))] = True
    return mask


def test_recorded_trace_against_brute_force():
    t = _slice()
    plane = next(iter(t.modules))
    ops = trace.clip(t.ops[plane], *t.window)
    busy = _grid(t, ops)
    assert trace.busy_s(t) == pytest.approx(busy.sum() * 1e-6, abs=2e-5)
    service = _grid(t, trace.spans_named(t, "chipbench.service"))
    assert trace.idle_in_service_s(t) == pytest.approx((service & ~busy).sum() * 1e-6, abs=2e-5)
    assert 0 < trace.busy_s(t) <= t.window_s


def test_recorded_trace_layers():
    """The names the chip's trace shows match the layer map: every decode
    program holds one attention kernel call per layer (28 for qwen3)."""
    t = _slice()
    plane = next(iter(t.modules))
    decode_s, n_decode = trace.device_time(t, "step.decode")
    kernel_s, n_kernel = trace.device_time(t, "kernel.decode_attention")
    assert n_decode >= 5 and 0 < kernel_s < decode_s
    whole = [m for m in t.modules[plane] if m[0].startswith("jit__arena_decode(")
             and t.window[0] < m[1] and m[2] < t.window[1]]
    assert n_kernel - 28 * len(whole) in range(0, 57)  # plus the cut programs at both ends
    assert decode_s == pytest.approx(sum(b - a for n, a, b in trace.clip(t.modules[plane], *t.window)
                                         if n.startswith("jit__arena_decode(")))
    b = trace.breakdown(t)
    assert len(b["device_ops"]) == 10 and not any(n.startswith("%while") for n, _ in b["device_ops"])
    assert sum(v for _, v in b["device_ops"]) <= trace.busy_s(t) + 1e-9
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(t.window_s - trace.busy_s(t))
