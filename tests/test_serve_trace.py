"""ServeLoop's own tracing: the phase spans of each tick on the profiler's
clock, the per-call work they carry as arguments, and the always-on
record of the slowest tick. A session at smoke width, arena mode, traced
on the CPU; the spans are read back from the ``.xplane.pb`` the profiler
writes."""

import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.data.dataset import SyntheticCorpus
from repro.launch.serve import Request, ServeLoop
from repro.models import model as M

CFG = get_config("qwen3-1.7b").reduced(num_layers=2, d_model=64, vocab_size=64)
RUN = RunConfig(remat="none", attention_impl="xla", ssd_chunk=16)
LENS = (6, 9, 12, 15)
ARENA_PHASES = {"serve.tick", "serve.decode", "serve.decode.sync", "serve.emit", "serve.pump",
                "serve.admit", "serve.prefill", "serve.first_token", "serve.slot_write"}


@pytest.fixture(scope="module")
def params():
    return M.init_model(jax.random.PRNGKey(0), CFG)


def _requests(n=7, gen=6):
    corpus = SyntheticCorpus(CFG.vocab_size, max(LENS), 0)
    return [Request(i, corpus.grain_tokens(i, 1)[0][: LENS[i % len(LENS)]], gen + i % 3)
            for i in range(n)]


class _Recorder:
    """Each prefill's prompt length and each arena decode call's valid
    positions of the active rows, read from the slot table as the
    benchmark's harness reads them (``len(prompt) + len(tokens)``), and,
    as the ground truth, from the arena's position vector."""

    def __init__(self, loop):
        self.prefills, self.valid, self.from_arena = [], [], []
        pre, dec = loop.prefill, loop._decode_arena

        def prefill(p, toks):
            self.prefills.append(int(toks.shape[1]))
            return pre(p, toks)

        def decode(p, arena, toks, act):
            self.valid.append([len(loop._by_id[rid].prompt) + len(loop._by_id[rid].tokens)
                               for rid in loop._slot_rid if rid is not None])
            self.from_arena.append(int((np.asarray(arena["pos"])[act] + 1).sum()))
            return dec(p, arena, toks, act)

        loop.prefill, loop._decode_arena = prefill, decode


def _serve(params, reqs, mode="arena", trace_dir=None):
    """One session through the session API, as a driver runs it: start
    empty, enqueue, tick to the end; under the profiler from the first
    enqueue when a directory is given."""
    loop = ServeLoop(CFG, RUN, params, batch=4, max_len=48, mode=mode)
    for n in sorted({len(r.prompt) for r in reqs}):
        loop.warm(n)
    rec = _Recorder(loop)
    loop.start([], t0=time.perf_counter())
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        for r in reqs:
            loop.enqueue(r)
        while loop.tick() != "done":
            pass
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return loop, rec


def _spans(trace_dir):
    """The ``serve.*`` host events of the trace: (name, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("serve-trace"))
    reqs = _requests()
    loop, rec = _serve(params, reqs, trace_dir=d)
    return loop, rec, reqs, _spans(d)


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def test_trace_holds_tick_spans_and_every_phase_nests_in_one(traced):
    _, _, _, spans = traced
    assert {s[0] for s in spans} == ARENA_PHASES
    ticks = [s for s in spans if s[0] == "serve.tick"]
    for s in spans:
        if s[0] != "serve.tick":
            assert any(_inside(s, t) for t in ticks), s
    admits = [s for s in spans if s[0] == "serve.admit"]
    for s in spans:
        if s[0] in ("serve.prefill", "serve.first_token", "serve.slot_write"):
            assert any(_inside(s, a) for a in admits), s


def test_span_arguments_are_the_recorded_work_of_each_call(traced):
    """``rows``/``kv_tokens`` of each decode span are the active rows and
    their valid positions, new token included, as the harness's slot-table
    read gives them and as the arena's own positions say; ``tokens`` of
    each admission is the prefilled prompt's length."""
    loop, rec, reqs, spans = traced
    decodes = [s[3] for s in spans if s[0] == "serve.decode"]
    assert len(decodes) == len(rec.valid) == loop.stats()["decode_calls"]
    assert [(a["rows"], a["kv_tokens"]) for a in decodes] == [(len(v), sum(v)) for v in rec.valid]
    assert [a["kv_tokens"] for a in decodes] == rec.from_arena
    admits = [s[3]["tokens"] for s in spans if s[0] == "serve.admit"]
    assert admits == rec.prefills == [len(r.prompt) for r in reqs]


def test_profiler_changes_no_token_and_no_count(params, traced):
    loop, _, traced_reqs, _ = traced
    reqs = _requests()
    plain, _ = _serve(params, reqs)
    assert [r.tokens for r in reqs] == [r.tokens for r in traced_reqs]
    keys = ("completed", "rejected", "decode_steps", "decode_calls", "slot_occupancy",
            "cancelled", "prefill_skipped", "sessions_evicted")
    a, b = plain.stats(), loop.stats()
    assert {k: a[k] for k in keys} == {k: b[k] for k in keys}


def test_slowest_tick_phases_fit_inside_it_and_start_resets_it(traced):
    loop, _, _, spans = traced
    slow = loop.stats()["slowest_tick"]
    assert slow["s"] > 0 and slow["at_s"] >= 0
    assert set(slow["phases"]) <= ARENA_PHASES - {"serve.tick"}
    assert all(v >= 0 for v in slow["phases"].values())
    assert sum(slow["phases"].values()) <= slow["s"] + 1e-12
    # it is the longest tick the trace shows, to the span's own overhead
    longest = max(s[2] - s[1] for s in spans if s[0] == "serve.tick") * 1e-9
    assert slow["s"] <= longest + 1e-6
    loop.start([], t0=0.0)
    assert loop.stats()["slowest_tick"] is None


def test_kv_tokens_follow_parked_session_slots(params, tmp_path):
    """A follow-up turn that reclaims its session's parked slot counts the
    positions the slot holds, not its own unprefilled prompt: the running
    count stays equal to the arena's position vector."""
    loop = ServeLoop(CFG, RUN, params, batch=2, max_len=48, mode="arena")
    loop.warm(LENS[0])
    rec = _Recorder(loop)
    d = str(tmp_path)
    corpus = SyntheticCorpus(CFG.vocab_size, max(LENS), 1)
    turn = lambda rid, end: Request(rid, corpus.grain_tokens(rid, 1)[0][: LENS[0]], 4,
                                    session_id=7, session_end=end)
    other = Request(9, corpus.grain_tokens(9, 1)[0][: LENS[0]], 10)
    jax.profiler.start_trace(d)
    try:
        loop.start([turn(0, False), other], t0=time.perf_counter())
        while loop.tick() != "done":
            pass
        loop.enqueue(turn(1, True))
        while loop.tick() != "done":
            pass
    finally:
        jax.profiler.stop_trace()
    assert loop.stats()["prefill_skipped"] == 1
    spans = _spans(d)
    assert [s[3]["kv_tokens"] for s in spans if s[0] == "serve.decode"] == rec.from_arena
    assert [s[3]["tokens"] for s in spans if s[0] == "serve.admit"] == [LENS[0], LENS[0], 0]


@pytest.mark.parametrize("mode", ["cohort", "serial"])
def test_legacy_modes_trace_tick_pump_and_admit_only(params, tmp_path, mode):
    reqs = _requests(n=4, gen=3)
    loop, _ = _serve(params, reqs, mode=mode, trace_dir=str(tmp_path))
    names = {s[0] for s in _spans(str(tmp_path))}
    assert names == {"serve.tick", "serve.pump", "serve.admit"}
    assert set(loop.stats()["slowest_tick"]["phases"]) <= names
