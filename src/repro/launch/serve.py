"""Batched serving driver: prefill + decode with continuous batching.

A request queue feeds a fixed-width decode batch; finished sequences free
their slot and the next request is admitted with its own prefill (the
vLLM-style slot model, minus paging — the cache is dense per slot).

**Admission is the simulator's policy layer** (PR 3): every request is
offered to an :class:`~repro.core.admission.AdmissionPolicy` from the same
``ADMISSION`` registry ``core/simulator.run_workload`` uses — a request is
just a tiny job whose work is its token budget, and the
:class:`~repro.core.admission.ClusterView` it is judged against is built
from *measured* decode throughput, the paper's §IV.a capacity discipline.
A policy tuned against the overload/churn presets drops in here unchanged
(``--admission slo_classes``); there is no serve-private admit path.

**Decode is token-level continuous batching** (``mode="arena"``, the
default): the replica owns one fixed-capacity KV arena —
``models.model.init_cache`` stacked ``batch`` slots wide — plus a free-slot
allocator. ``decode_step`` takes a per-slot *position vector* and an
active-slot mask, so every occupied slot advances in **one dispatch per
step regardless of length mix**; a request joins by writing its prefilled
cache into a free slot (``jax.lax.dynamic_update_slice`` on a traced slot
index — no recompile, no ``_cat``/``jnp.take`` regroup churn) and leaves by
marking the slot free at a token boundary. Greedy sampling (argmax) is
fused into the jitted decode call, so the host round-trip per step is
``batch`` token ids, not a logits tensor. ``stats()`` reports
``decode_calls`` (== steps taken) and ``slot_occupancy`` (mean active
fraction per call) so a run shows exactly how much batching it got.

**Weights** are held as ``models.model.serving_params`` makes them from
the tree the loop is given: every matrix cast once to the compute dtype,
the vectors as they were. The step programs then convert no weight in
any call; ``stats()["served_param_bytes"]`` says what they read.

Two legacy modes remain selectable: ``mode="cohort"`` is the PR-3
position-grouped path (uniform lengths batch well; mixed lengths degrade
toward per-slot dispatch — the regime claim 14 in
``benchmarks/bench_decode.py`` measures the arena against), and
``mode="serial"`` (the ``--no-batch`` escape hatch) decodes each slot in
its own dispatch — the bit-exact single-request reference the continuous-
batching tests compare token streams against.

**Tracing.** Every ``tick()`` is a ``serve.tick`` span of
``jax.profiler``, so a trace puts the host's phases on the device's clock.
Inside it, arena mode opens ``serve.decode`` (arguments ``rows``, the
active slots, and ``kv_tokens``, their valid cache positions with the new
token's), ``serve.decode.sync``, ``serve.emit``, ``serve.pump``, and per
admitted request ``serve.admit`` (argument ``tokens``, the prompt length,
0 on a parked-slot hit) holding ``serve.prefill``, ``serve.first_token``
and ``serve.slot_write``; the legacy modes open ``serve.pump`` and
``serve.admit`` alone. With no profiler running a span costs about a
microsecond. The same timers keep, always on, the session's longest tick
with each phase's own seconds (``stats()["slowest_tick"]``).

Caveat: the arena masks *positions*, not expert routing — on MoE
architectures parked slots still consume router capacity, so arena mode is
exact for attention/SSM stacks and approximate under MoE capacity drops
(the eval capacity factor leaves headroom; serving benches use attention
architectures).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b-smoke \
      --requests 16 --batch 4 --prompt-len 32 --gen 16 \
      --admission slo_classes --mode arena
"""

from __future__ import annotations

import argparse
import contextlib
import heapq
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core.admission import (
    ADMIT,
    DEFER,
    AdmissionPolicy,
    ClusterView,
    JobRequest,
    get_policy,
    trailing_class_p99,
)
from repro.data.dataset import SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    submitted: float = 0.0  # admit time (slot granted; prefill starts)
    first_token: float = -1.0
    finished: float = -1.0
    tokens: list[int] = field(default_factory=list)
    # admission handles (PR 3): arrival is stamped at *enqueue*, so TTFT and
    # latency include queueing + deferral — admission control is meaningless
    # if the wait it imposes is invisible to the metrics.
    arrived: float = -1.0
    slo_class: int = 0
    deadline_s: float = math.inf
    rejected: bool = False
    # multi-turn session identity (PR 10): turns of one conversation share a
    # session_id; the arena parks the session's KV slot between turns so a
    # follow-up admitted here skips re-prefill. session_end marks the last
    # turn — its completion frees the slot instead of parking it.
    session_id: int = -1
    session_end: bool = False

    @property
    def queue_wait(self) -> float:
        return self.submitted - self.arrived

    def clone_for_hedge(self) -> "Request":
        """A second attempt of this request, for hedged dispatch (PR 6).

        Same ``rid`` — the fleet's books are keyed by rid and first-
        completion-wins is resolved there — but a fresh token list and
        timing fields, because each replica session mutates the
        ``Request`` it holds: two replicas must never share one mutable
        object. Admission identity (arrival stamp, class, deadline)
        carries over, so the clone is never re-judged and races as the
        same logical request."""
        return Request(
            rid=self.rid,
            prompt=self.prompt,
            max_new=self.max_new,
            arrived=self.arrived,
            slo_class=self.slo_class,
            deadline_s=self.deadline_s,
            session_id=self.session_id,
            session_end=self.session_end,
        )


class _Group:
    """Cohort-mode slots whose caches share a position, stacked along the
    batch axis (the PR-3 path, kept as the claim-14 baseline).

    ``cache["layers"]`` leaves are ``(n_layer_periods, B, ...)`` (the layer
    dim comes from the prefill scan), so batch concatenation/indexing is on
    axis 1. ``pos`` is tracked host-side and mirrors the per-slot
    ``cache["pos"]`` vector, whose entries a group keeps equal by
    construction — that shared position is the grouping key.
    """

    __slots__ = ("pos", "rids", "cache", "last")

    def __init__(self, pos: int, rids: list[int], cache, last: list[int]):
        self.pos, self.rids, self.cache, self.last = pos, rids, cache, last


def _cat(a, b):
    layers = jax.tree.map(
        lambda x, y: jnp.concatenate([x, y], axis=1), a["layers"], b["layers"]
    )
    return {"pos": jnp.concatenate([a["pos"], b["pos"]]), "layers": layers}


def _take(cache, idx: list[int]):
    sel = jnp.asarray(idx)
    return {
        "pos": jnp.take(cache["pos"], sel),
        "layers": jax.tree.map(lambda x: jnp.take(x, sel, axis=1), cache["layers"]),
    }


def _slot_write(arena, one, slot):
    """Write a freshly prefilled single-request cache into arena slot
    ``slot`` — ``dynamic_update_slice`` on a *traced* slot index, so one
    compile serves every slot and joins never trigger the `_cat`-shaped
    recompile-and-regroup churn the cohort path pays."""
    layers = jax.tree.map(
        lambda a, o: jax.lax.dynamic_update_slice_in_dim(
            a, o.astype(a.dtype), slot, axis=1
        ),
        arena["layers"],
        one["layers"],
    )
    pos = jax.lax.dynamic_update_slice_in_dim(
        arena["pos"], one["pos"].astype(arena["pos"].dtype), slot, axis=0
    )
    return {"pos": pos, "layers": layers}


def _tree_bytes(tree) -> dict[str, int]:
    out: dict[str, int] = {}
    for x in jax.tree.leaves(tree):
        out[x.dtype.name] = out.get(x.dtype.name, 0) + int(x.nbytes)
    return out


class _Phase:
    """One phase of a tick (or of ``start()``'s first admissions). It opens
    a ``jax.profiler.TraceAnnotation`` (a host span on the device trace's
    clock while the profiler runs, about a microsecond when it does not)
    and adds its own ``perf_counter`` seconds, less those of the phases
    opened inside it, to ``split[name]``. ``open_`` is the stack of the
    phases open around it."""

    __slots__ = ("_name", "_split", "_open", "_ann", "_t", "_inner")

    def __init__(self, name: str, split: dict, open_: list, **args):
        self._name, self._split, self._open = name, split, open_
        self._ann = jax.profiler.TraceAnnotation(name, **args)

    def note(self, **args) -> None:
        """Arguments known only once the phase is under way."""
        self._ann.set_metadata(**args)

    def __enter__(self) -> "_Phase":
        self._ann.__enter__()
        self._open.append(self)
        self._inner = 0.0
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t
        self._open.pop()
        if self._open:
            self._open[-1]._inner += dt
        self._split[self._name] = self._split.get(self._name, 0.0) + dt - self._inner
        self._ann.__exit__(*exc)


class ServeLoop:
    """Single-replica continuous batching behind a shared admission policy.

    PR 4 splits the monolithic ``run_requests`` into an incremental session
    API so ``launch/fleet.py`` can interleave N replicas on one host:
    :meth:`start` opens a session, :meth:`tick` advances it by one
    scheduling/decode cycle, :meth:`stats` closes it; :meth:`enqueue` /
    :meth:`cancel` are the fleet hooks (route a request in, pull a stuck
    one out for LATE-style re-dispatch). ``run_requests`` is now a thin
    start/tick/stats wrapper with unchanged semantics.
    """

    def __init__(
        self,
        cfg,
        run,
        params,
        batch: int,
        max_len: int,
        admission: Union[str, AdmissionPolicy, None] = "admit_all",
        batched: bool = True,
        warmup: bool = True,
        mode: Optional[str] = None,
    ):
        self.cfg, self.run = cfg, run
        # the step programs read matrices in the compute dtype: cast once
        # here, and hold no reference to the float32 tree (the caller may
        # free it), rather than convert every matrix in every call
        self.params = M.serving_params(cfg, params)
        self.batch = batch
        self.max_len = max_len
        self.admission = admission
        # mode: "arena" (token-level continuous batching, default) |
        # "cohort" (PR-3 position groups) | "serial" (per-slot dispatch).
        # `batched` is the legacy knob: batched=False is exactly "serial".
        if mode is None:
            mode = "arena" if batched else "serial"
        if mode not in ("arena", "cohort", "serial"):
            raise ValueError(f"unknown serve mode {mode!r}")
        self.mode = mode
        self.batched = mode != "serial"
        self.warmup = warmup
        self.prefill = jax.jit(
            lambda p, toks: M.prefill(cfg, run, p, toks, max_len, None)
        )
        self.decode = jax.jit(
            lambda p, c, toks: M.decode_step(cfg, run, p, c, toks, None)
        )

        def _arena_decode(p, c, toks, act):
            logits, new_cache = M.decode_step(cfg, run, p, c, toks, None, active=act)
            return jnp.argmax(logits[:, -1, :], axis=-1), new_cache

        # greedy sampling fused into the dispatch: the per-step host
        # round-trip is `batch` token ids, not a (B, 1, vocab) logits pull
        self._decode_arena = jax.jit(_arena_decode)
        self._write_slot = jax.jit(_slot_write)

    def _new_arena(self):
        """A zeroed arena on the params' device. The replica runs where its
        params live (the fleet places each replica on its own chip): the
        arena is built there, and host inputs go to the jitted steps as
        numpy so that they land beside the params, not on device 0."""
        device = next(iter(jax.tree.leaves(self.params)[0].devices()))
        with jax.default_device(device):
            return M.init_cache(self.cfg, self.batch, self.max_len)

    def _warm(self, prompt_len: int) -> None:
        """Compile prefill (B=1) and decode at every group width once,
        *before* the measured window opens: a first-hit XLA compile inside
        the serve loop stalls decoding mid-run and lands a compile-dominated
        sample in the capacity EMA — which capacity-gated policies then
        act on permanently (an offer is final)."""
        tok = np.zeros((1, prompt_len), np.int32)
        _, cache = self.prefill(self.params, tok)
        if self.mode == "arena":
            # one decode width exists (the full arena) — compile the slot
            # write and the fused decode+argmax once; a throwaway arena so
            # repeated warms (one per distinct prompt length) stay cheap
            arena = self._write_slot(self._new_arena(), cache, 0)
            act = np.zeros((self.batch,), bool)
            act[0] = True
            self._decode_arena(
                self.params, arena, np.zeros((self.batch, 1), np.int32), act
            )
            return
        widths = range(1, self.batch + 1) if self.batched else (1,)
        c = cache
        for b in widths:
            if b > 1:
                c = _cat(c, cache)
            self.decode(self.params, c, jnp.zeros((b, 1), jnp.int32))

    def warm(self, prompt_len: int) -> None:
        """Public pre-compile hook for shared-clock callers: a fleet warms
        every replica *before* opening the shared measurement clock, so
        compile time stays outside the measured window (the PR-3 rule,
        fleet-wide)."""
        if self.warmup:
            self._warm(prompt_len)

    # -- session lifecycle ----------------------------------------------

    def start(
        self,
        requests: list[Request],
        prompt_len: Optional[int] = None,
        t0: Optional[float] = None,
    ) -> None:
        """Open a serving session over ``requests`` (may be empty when a
        fleet front-end will :meth:`enqueue` routed requests later —
        ``prompt_len`` then sizes the compile warm-up). ``t0`` is a shared
        ``perf_counter`` origin: a fleet passes one clock to every replica
        so arrival stamps (fleet door) and finish stamps (replica) subtract
        on the same timeline — a shared-clock caller owns the warm-up
        (:meth:`warm` before opening the clock); standalone sessions warm
        here and open their own origin afterwards."""
        self._policy = get_policy(self.admission)  # fresh state per run
        warm_len = prompt_len or (
            int(requests[0].prompt.shape[0]) if requests else 0
        )
        if self.warmup and warm_len and t0 is None:
            self._warm(warm_len)
        self._t0 = time.perf_counter() if t0 is None else t0
        self._requests: list[Request] = list(requests)
        for r in self._requests:
            if r.arrived < 0:
                r.arrived = self.now()  # enqueue stamp (0.0 upfront)
        self._by_id = {r.rid: r for r in self._requests}
        self._pending = deque(self._requests)  # not yet offered to policy
        self._ready: deque[Request] = deque()  # admitted, awaiting a slot
        self._rejected: list[Request] = []
        self._groups: list[_Group] = []
        # arena state: rid per slot (None = free), last emitted token per
        # slot, ascending free-slot heap (lowest slot wins — deterministic),
        # and the stacked cache itself (lazy: first admit builds it)
        self._slot_rid: list[Optional[int]] = [None] * self.batch
        self._slot_last = np.zeros(self.batch, np.int64)
        self._free_slots = list(range(self.batch))
        self._arena = None
        # session residency (PR 10): a finished turn whose session is still
        # live *parks* its slot (cache bytes stay) instead of freeing it —
        # session_id → slot, insertion-ordered so the first entry is the
        # least-recently-parked and is the LRU eviction victim under slot
        # pressure. Parked slots are in neither _free_slots nor _slot_rid.
        self._session_slot: dict[int, int] = {}
        self._prefill_skipped = 0
        self._sessions_evicted = 0
        self._occ_sum = 0  # Σ active slots over decode calls
        self._done_hist: dict[int, list[float]] = {}  # sojourns per class
        self._decode_tokens = 0
        self._decode_calls = 0
        self._cancelled = 0
        self._offered = 0
        # measured decode throughput (tokens/s), EMA over per-step rates
        # timed around the decode calls only — a from-start average would
        # fold jit compile and idle waits into "capacity" and mis-rate the
        # threshold/token_bucket policies by an order of magnitude
        self._tok_rate = 0.0
        self._peak_rate = 0.0
        # phase split of the current tick (own seconds per span name), the
        # phases open now, and the longest tick so far with its split
        self._split: dict[str, float] = {}
        self._open: list[_Phase] = []
        self._slowest: Optional[dict] = None
        # Σ valid cache positions of the active slots, kept as a running
        # count: slot s holds _slot_base[s] + len(tokens of its request)
        self._kv_tokens = 0
        self._slot_base = np.zeros(self.batch, np.int64)
        self._pump()
        self._fill_slots()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def _phase(self, name: str, **args) -> _Phase:
        return _Phase(name, self._split, self._open, **args)

    def _inner_phase(self, name: str, **args):
        """A phase that only arena mode splits out; the legacy modes trace
        their tick, pump and admissions alone."""
        if self.mode == "arena":
            return self._phase(name, **args)
        return contextlib.nullcontext()

    @property
    def tok_rate(self) -> float:
        """Measured decode throughput EMA — the capacity this replica
        reports to a fleet router (the §IV.a measured-rate currency)."""
        return self._tok_rate

    @property
    def peak_rate(self) -> float:
        """Fastest EMA observed this session: the fleet's stand-in for a
        nameplate rate (real replicas register no spec sheet)."""
        return self._peak_rate

    def _active_count(self) -> int:
        if self.mode == "arena":
            # parked session slots hold cache bytes but decode nothing:
            # they are not active (and not free — they're evictable)
            return sum(1 for rid in self._slot_rid if rid is not None)
        return sum(len(g.rids) for g in self._groups)

    def resident_sessions(self) -> frozenset:
        """Sessions whose KV cache is parked in this replica's arena — the
        residency set the fleet's ``affinity`` router keys on."""
        return frozenset(self._session_slot)

    def _decoding_rids(self) -> list[int]:
        """Rids currently holding a decode slot, slot/decode order."""
        if self.mode == "arena":
            return [rid for rid in self._slot_rid if rid is not None]
        return [rid for g in self._groups for rid in g.rids]

    def outstanding_rids(self) -> list[int]:
        """Requests decoding or admitted-and-waiting, decode order first —
        what a fleet re-dispatch monitor watches for stuck entries."""
        return self._decoding_rids() + [r.rid for r in self._ready]

    def queued_rids(self) -> list[int]:
        """Admitted-but-not-yet-decoding requests, queue order. These are
        movable at zero cost (no generated tokens to discard): the fleet's
        spawn-time rebalance pulls from here when autoscaling adds a
        replica (launch/fleet.py)."""
        return [r.rid for r in self._ready]

    def backlog_tokens(self) -> float:
        """Remaining token budget across decoding + ready requests — the
        backlog the fleet's ``shortest_backlog`` router joins on."""
        live = [self._by_id[rid] for rid in self._decoding_rids()]
        return float(
            sum(r.max_new - len(r.tokens) for r in live)
            + sum(r.max_new for r in self._ready)
        )

    @property
    def idle(self) -> bool:
        return self._active_count() == 0 and not self._ready

    # -- fleet hooks -----------------------------------------------------

    def enqueue(self, r: Request) -> None:
        """Route an already-admitted request onto this replica (the fleet
        front door did the admission; no second policy pass here)."""
        if r.arrived < 0:
            r.arrived = self.now()
        if r.rid not in self._by_id:
            self._requests.append(r)
        self._by_id[r.rid] = r
        self._ready.append(r)

    def cancel(self, rid: int) -> bool:
        """Pull a request out of this replica (LATE-style re-dispatch
        cancels the original attempt). Generated tokens are discarded by
        the caller before re-enqueueing elsewhere; returns False when the
        request is not outstanding here (it finished first — the race the
        router property test pins). The request leaves this session's
        books entirely: whichever replica it finishes on is the only one
        that counts it in :meth:`stats`."""
        found = False
        for r in list(self._ready):
            if r.rid == rid:
                self._ready.remove(r)
                found = True
                break
        if not found and self.mode == "arena":
            # mid-decode cancel (hedge loser / re-dispatch): just free the
            # slot — the cache bytes stay until the next join overwrites
            # them, which is the whole point of the allocator
            for s, orid in enumerate(self._slot_rid):
                if orid == rid:
                    self._leave(s, self._by_id[rid])
                    self._release_slot(s)
                    found = True
                    break
        if not found:
            for g in self._groups:
                if rid in g.rids:
                    keep = [i for i, x in enumerate(g.rids) if x != rid]
                    if not keep:
                        self._groups.remove(g)
                    else:
                        g.cache = _take(g.cache, keep)
                        g.rids = [g.rids[i] for i in keep]
                        g.last = [g.last[i] for i in keep]
                    found = True
                    break
        if found:
            req = self._by_id.get(rid)
            # bugfix (PR 10): a cancelled request leaves this replica for
            # good (hedge loser / re-dispatch) — but its *session's* parked
            # slot from a previous turn would otherwise linger in the
            # allocator map forever, pinning a slot for a conversation that
            # now lives on another replica. Evict the residency too.
            sid = getattr(req, "session_id", -1) if req is not None else -1
            if sid is not None and sid >= 0:
                parked = self._session_slot.pop(sid, None)
                if parked is not None:
                    self._release_slot(parked)
            self._requests = [x for x in self._requests if x.rid != rid]
            self._by_id.pop(rid, None)
            self._cancelled += 1
        return found

    # -- admission protocol (same registry as run_workload) --------------

    def _view(self, t: float) -> ClusterView:
        # before the first measurement, capacity is *unbounded*: an offer
        # is a permanent decision, and the door must never shed work on a
        # fabricated slot-count guess — _pump() bounds how many requests
        # are judged optimistically to one batch
        cap = self._tok_rate if self._tok_rate > 0 else float("inf")
        return ClusterView(
            time=t,
            live_capacity=cap,
            total_capacity=cap,
            free_slots=self.batch - self._active_count(),
            queue_depth=self._active_count() + len(self._ready),
            backlog_work=self.backlog_tokens(),
            deferred_depth=self._policy.n_deferred if self._policy else 0,
            deferred_work=self._policy.deferred_work if self._policy else 0.0,
            class_p99=trailing_class_p99(self._done_hist),
        )

    @staticmethod
    def as_job_request(r: Request) -> JobRequest:
        return JobRequest(
            job_id=r.rid,
            arrive_t=r.arrived,
            n_tasks=1,
            total_work=float(r.max_new),
            slo_class=r.slo_class,
            deadline_s=r.deadline_s,
            session_id=r.session_id,
        )

    def _resolve(self, r: Request, decision: str) -> None:
        if decision == ADMIT:
            self._ready.append(r)
        else:
            r.rejected = True
            self._rejected.append(r)

    def _pump(self, force: bool = False) -> None:
        """Offer new arrivals, then drain whatever the policy releases —
        the exact protocol run_workload speaks; no serve-private logic.

        Until the first decode step has produced a *measured* capacity,
        at most one batch of requests is offered (against the
        optimistic unbounded view): enough to start decoding and get a
        real measurement, without judging the whole queue on a guess.
        ``force`` lifts the bound for the endgame drain — when nothing
        will ever run again, the guess is all there is."""
        with self._phase("serve.pump"):
            if self._policy is None:
                while self._pending:
                    self._ready.append(self._pending.popleft())
                return
            while self._pending:
                if self._tok_rate <= 0 and not force and self._offered >= self.batch:
                    break
                r = self._pending.popleft()
                self._offered += 1
                decision = self._policy.offer(self.as_job_request(r), self._view(self.now()))
                if decision != DEFER:
                    self._resolve(r, decision)
            for req, decision in self._policy.poll(self._view(self.now())):
                self._resolve(self._by_id[req.job_id], decision)

    def _on_done(self, r: Request) -> None:
        sojourn = r.finished - r.arrived
        self._done_hist.setdefault(r.slo_class, []).append(sojourn)
        if self._policy is not None:
            self._policy.on_job_done(self.now(), self.as_job_request(r), sojourn)

    # -- decode mechanics -------------------------------------------------

    def _release_slot(self, s: int) -> None:
        self._slot_rid[s] = None
        heapq.heappush(self._free_slots, s)

    def _leave(self, s: int, r: Request) -> None:
        """Slot ``s``'s request ``r`` stops decoding: its positions leave
        the running count."""
        self._kv_tokens -= int(self._slot_base[s]) + len(r.tokens)

    def _admit(self, r: Request) -> None:
        r.submitted = self.now()
        hit = self.mode == "arena" and r.session_id >= 0 and r.session_id in self._session_slot
        pos = int(r.prompt.shape[0])
        with self._phase("serve.admit", tokens=0 if hit else pos):
            if hit:
                # cache hit: the session's slot is parked here from its
                # previous turn — reclaim it and keep decoding from the
                # resident cache, skipping the whole re-prefill dispatch. The
                # slot's last token is still in _slot_last, so the decode
                # step continues exactly where the prior turn left off.
                s = self._session_slot.pop(r.session_id)
                self._slot_rid[s] = r.rid
                self._kv_tokens += int(self._slot_base[s])
                self._prefill_skipped += 1
                return
            with self._inner_phase("serve.prefill"):
                logits, cache = self.prefill(
                    self.params, np.asarray(r.prompt[None], np.int32)
                )
            with self._inner_phase("serve.first_token"):
                tok = int(jnp.argmax(logits[0, -1]))
            r.tokens.append(tok)
            r.first_token = self.now()
            if self.mode == "arena":
                # join at a token boundary: claim the lowest free slot,
                # index-write the prefilled cache in — no regroup, no
                # recompile
                if self._arena is None:
                    self._arena = self._new_arena()
                if not self._free_slots and self._session_slot:
                    # slot pressure: evict the least-recently-parked
                    # session — a live decode always outranks a
                    # speculative future turn
                    old_sid = next(iter(self._session_slot))
                    self._release_slot(self._session_slot.pop(old_sid))
                    self._sessions_evicted += 1
                s = heapq.heappop(self._free_slots)
                self._slot_rid[s] = r.rid
                self._slot_last[s] = tok
                # the cache holds the prompt; the first token is fed next step
                self._slot_base[s] = pos - 1
                self._kv_tokens += pos
                with self._phase("serve.slot_write"):
                    self._arena = self._write_slot(self._arena, cache, s)
                return
            if self.mode == "cohort":
                for g in self._groups:
                    if g.pos == pos and len(g.rids) < self.batch:
                        g.cache = _cat(g.cache, cache)
                        g.rids.append(r.rid)
                        g.last.append(tok)
                        return
            self._groups.append(_Group(pos, [r.rid], cache, [tok]))

    def _fill_slots(self) -> None:
        while self._ready and self._active_count() < self.batch:
            self._admit(self._ready.popleft())

    def _merge_groups(self) -> None:
        """Coalesce groups whose positions have come to coincide (a
        group drained and a later admit landed on the same position) —
        without this they'd pay separate dispatches forever."""
        by_pos: dict[int, _Group] = {}
        for g in list(self._groups):
            head = by_pos.get(g.pos)
            if head is None or len(head.rids) + len(g.rids) > self.batch:
                by_pos[g.pos] = g
                continue
            head.cache = _cat(head.cache, g.cache)
            head.rids += g.rids
            head.last += g.last
            self._groups.remove(g)

    def _step_arena(self) -> np.ndarray:
        """One decode step for the whole arena: a single dispatch advances
        every occupied slot, whatever mix of positions they sit at. Returns
        the new token of every slot."""
        with self._phase("serve.decode") as span:
            act = np.array([rid is not None for rid in self._slot_rid])
            rows = int(act.sum())
            self._kv_tokens += rows  # each active row writes one position
            span.note(rows=rows, kv_tokens=self._kv_tokens)
            toks = self._slot_last[:, None].astype(np.int32)
            new_toks, self._arena = self._decode_arena(
                self.params, self._arena, toks, act
            )
        self._decode_calls += 1
        self._occ_sum += rows
        with self._phase("serve.decode.sync"):
            return np.asarray(new_toks)

    def _emit_arena(self, new: np.ndarray) -> None:
        """Hand each active slot its new token; finish, park or free the
        slots whose requests are done."""
        t_step = self.now()
        for s, rid in enumerate(list(self._slot_rid)):
            if rid is None:
                continue
            r = self._by_id[rid]
            tok = int(new[s])
            r.tokens.append(tok)
            if r.first_token < 0:
                # cache-hit admits skip prefill, so their first token is the
                # first decode append, not a prefill argmax
                r.first_token = t_step
            self._slot_last[s] = tok
            self._decode_tokens += 1
            if len(r.tokens) >= r.max_new:
                r.finished = t_step
                self._on_done(r)
                self._leave(s, r)
                if r.session_id >= 0 and not r.session_end:
                    # park: the session has more turns coming — keep the
                    # cache resident so the follow-up can skip re-prefill
                    # (the next turn's tokens count on from what it holds)
                    self._slot_rid[s] = None
                    self._slot_base[s] += len(r.tokens)
                    old = self._session_slot.pop(r.session_id, None)
                    if old is not None and old != s:
                        self._release_slot(old)
                    self._session_slot[r.session_id] = s
                else:
                    if r.session_id >= 0:
                        self._session_slot.pop(r.session_id, None)
                    self._release_slot(s)

    def _step_groups(self) -> None:
        if self.mode == "cohort" and len(self._groups) > 1:
            self._merge_groups()
        for g in list(self._groups):
            toks = jnp.asarray(np.asarray(g.last, np.int32)[:, None])
            logits, g.cache = self.decode(self.params, g.cache, toks)
            self._decode_calls += 1
            self._occ_sum += len(g.rids)
            new = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
            t_step = self.now()
            keep: list[int] = []
            for i, rid in enumerate(g.rids):
                r = self._by_id[rid]
                tok = int(new[i])
                r.tokens.append(tok)
                g.last[i] = tok
                self._decode_tokens += 1
                if len(r.tokens) >= r.max_new:
                    r.finished = t_step
                    self._on_done(r)
                else:
                    keep.append(i)
            g.pos += 1
            if len(keep) < len(g.rids):
                if not keep:
                    self._groups.remove(g)
                else:
                    g.cache = _take(g.cache, keep)
                    g.rids = [g.rids[i] for i in keep]
                    g.last = [g.last[i] for i in keep]

    def _step(self) -> None:
        t_in, toks_in = time.perf_counter(), self._decode_tokens
        if self.mode == "arena":
            new = self._step_arena()
            with self._phase("serve.emit"):
                self._emit_arena(new)
                self._measure_rate(t_in, toks_in)
        else:
            self._step_groups()
            self._measure_rate(t_in, toks_in)

    def _measure_rate(self, t_in: float, toks_in: int) -> None:
        inst = (self._decode_tokens - toks_in) / max(
            time.perf_counter() - t_in, 1e-9
        )
        self._tok_rate = (
            inst if self._tok_rate <= 0 else 0.8 * self._tok_rate + 0.2 * inst
        )
        self._peak_rate = max(self._peak_rate, self._tok_rate)
        if self._policy is not None:
            # the same capacity signal the simulator's churn chain
            # emits: token_bucket re-rates its fill to measured tok/s
            self._policy.on_capacity(self.now(), self._tok_rate)

    # -- the session stepper ----------------------------------------------

    def tick(self) -> str:
        """Advance one scheduling/decode cycle.

        Returns ``"step"`` (made progress), ``"wait"`` (deferred requests
        exist but the policy released nothing — the caller owns the
        wall-clock and decides whether to sleep), or ``"done"``.

        The tick is a ``serve.tick`` span, and the longest one of the
        session is kept with the own seconds of each phase inside it
        (``stats()["slowest_tick"]``)."""
        self._split = {}
        with jax.profiler.TraceAnnotation("serve.tick"):
            t = time.perf_counter()
            status = self._tick()
            s = time.perf_counter() - t
        if self._slowest is None or s > self._slowest["s"]:
            self._slowest = {"at_s": t - self._t0, "s": s, "phases": self._split}
        return status

    def _tick(self) -> str:
        if self._active_count() == 0:
            if self._ready:
                self._fill_slots()
                return "step"
            if self._policy is not None and self._policy.n_deferred:
                self._pump()
                self._fill_slots()
                return (
                    "step"
                    if (self._active_count() or self._ready)
                    else "wait"
                )
            if self._pending:
                # endgame: nothing running or deferred but requests were
                # never offered (the pre-measurement bound) — drain them
                self._pump(force=True)
                self._fill_slots()
                if self._active_count() or self._ready:
                    return "step"
            return "done"
        self._step()
        self._pump()
        self._fill_slots()
        return "step"

    def stats(self) -> dict:
        wall = time.perf_counter() - self._t0
        done = [r for r in self._requests if r.finished >= 0]
        policy = self._policy
        return {
            "completed": len(done),
            "rejected": len(self._rejected),
            "deferred_unserved": policy.n_deferred if policy else 0,
            "admission": policy.name if policy else "none",
            "mode": self.mode,
            "wall_s": wall,
            "decode_steps": self._decode_tokens,
            "decode_calls": self._decode_calls,
            # mean fraction of the batch doing useful work per dispatch —
            # arena mode's whole claim is that this stays high under mixed
            # lengths while decode_calls stays at one per step
            "slot_occupancy": (
                self._occ_sum / (self._decode_calls * self.batch)
                if self._decode_calls
                else 0.0
            ),
            "cancelled": self._cancelled,
            # session residency (PR 10): prefills skipped via a parked slot
            # and parked sessions LRU-evicted under slot pressure
            "prefill_skipped": self._prefill_skipped,
            "sessions_evicted": self._sessions_evicted,
            # the longest tick: its start on now()'s clock, its seconds, and
            # the own seconds of each phase inside it (the rest is the
            # tick's own code); None before the first tick
            "slowest_tick": self._slowest,
            # {dtype name: bytes} of the parameter tree the step programs
            # read: the matrices in the compute dtype, the vectors as given
            "served_param_bytes": _tree_bytes(self.params),
            "tokens_per_s": sum(len(r.tokens) for r in done) / wall if wall else 0.0,
            "mean_ttft_s": float(np.mean([r.first_token - r.arrived for r in done])) if done else -1,
            "mean_latency_s": float(np.mean([r.finished - r.arrived for r in done])) if done else -1,
            "mean_queue_wait_s": float(np.mean([r.queue_wait for r in done])) if done else -1,
        }

    def run_requests(self, requests: list[Request], greedy: bool = True) -> dict:
        """Standalone session: start → tick to completion → stats.
        Semantics identical to the pre-PR-4 monolithic loop."""
        self.start(requests)
        last_progress = time.perf_counter()
        while True:
            status = self.tick()
            if status == "done":
                break
            if status == "wait":
                # nothing running: wall-clock has to pay the token debt
                nxt = self._policy.next_event_t()
                wait = 0.01 if nxt is None else max(0.0, min(nxt - self.now(), 0.25))
                time.sleep(wait)
                if time.perf_counter() - last_progress > 60.0:
                    break  # a policy that never releases: report, don't hang
            else:
                last_progress = time.perf_counter()
        return self.stats()


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b-smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--admission", default="admit_all",
                    help="policy name from core.admission.ADMISSION")
    ap.add_argument("--mode", default=None,
                    choices=["arena", "cohort", "serial"],
                    help="decode batching: arena (continuous, default), "
                         "cohort (PR-3 position groups), serial (per-slot)")
    ap.add_argument("--no-batch", action="store_true",
                    help="alias for --mode serial: per-slot decode, the "
                         "bit-exact single-request reference path")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    run = RunConfig(remat="none", attention_impl="xla", ssd_chunk=min(256, args.prompt_len))
    params = M.init_model(jax.random.PRNGKey(args.seed), cfg)

    corpus = SyntheticCorpus(cfg.vocab_size, args.prompt_len, args.seed)
    reqs = [
        Request(i, corpus.grain_tokens(i, 1)[0], args.gen) for i in range(args.requests)
    ]
    loop = ServeLoop(
        cfg, run, params, args.batch, args.prompt_len + args.gen + 1,
        admission=args.admission, batched=not args.no_batch, mode=args.mode,
    )
    stats = loop.run_requests(reqs)
    slow = stats["slowest_tick"]
    phases = ", ".join(
        f"{k} {v * 1e3:.1f}" for k, v in sorted(slow["phases"].items(), key=lambda kv: -kv[1])
    )
    print(
        f"served {stats['completed']}/{args.requests} requests "
        f"(rejected {stats['rejected']}, admission={stats['admission']}, "
        f"mode={stats['mode']})  "
        f"{stats['tokens_per_s']:.1f} tok/s in {stats['decode_calls']} decode calls "
        f"(occupancy {stats['slot_occupancy']:.2f})  "
        f"ttft={stats['mean_ttft_s']*1e3:.0f}ms  "
        f"latency={stats['mean_latency_s']*1e3:.0f}ms  "
        f"slowest tick {slow['s'] * 1e3:.1f}ms at {slow['at_s']:.3f}s ({phases} ms)"
    )
    return stats


if __name__ == "__main__":
    main()
