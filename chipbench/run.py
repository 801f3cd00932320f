"""The chip benchmark's one command.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell (``cells/<cell>.json``: configuration, mix, chips, rate or
clients, arena, limits), makes the weights on the device from the seed,
builds the program's ``ServeLoop``, warms exactly the cell's prompt
buckets and the decode step (set-up, reported as ``setup_s``), drives the
window for ``--seconds``, then checks what was served against the
float32 reference and prints one JSON line last on stdout. With
``--trace 0`` the line's metrics are the end-to-end ones; with
``--trace 1`` the last ``trace_seconds`` of the window run under the
profiler and the metrics are the per-layer readers of ``metrics/``.

Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero and prints no result: it never falls back to the CPU.
"""

import time

T_START = time.perf_counter()  # set-up is timed from here: imports count

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import spec  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(Exception):
    pass


@dataclass
class Context:
    """What a per-layer metric reader reads."""

    config: dict
    records: list
    close: float
    peak: dict
    trace: object = None
    prefill_calls: list = field(default_factory=list)  # prompt lengths
    decode_calls: list = field(default_factory=list)  # valid positions of active rows
    stats: dict = field(default_factory=dict)  # loop.stats() at the close
    spans: list = field(default_factory=list)  # the program's serve.* spans in the window (trace.Span)


def require_chip(jax, chips: int) -> dict:
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found {dev}")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {dev}")
    return dev


def warm(loop, buckets: list[int]) -> None:
    """Compile every program the window runs: the prefill of each of the
    cell's prompt buckets, the slot write and the decode step (the
    program's ``warm``), then one request through a session for the
    operations the admission path runs outside those programs."""
    import numpy as np

    from chipbench.system import Request

    for n in buckets:
        loop.warm(n)
    loop.start([], t0=time.perf_counter())
    loop.enqueue(Request(-1, np.zeros(buckets[0], np.int32), 2))
    while loop.tick() != "done":
        pass


class Tracer:
    """The profiler over the last part of the window, with the harness's
    host spans and its record of each prefill and decode call."""

    def __init__(self, jax, loop, out_dir: str):
        self.jax, self.loop, self.dir = jax, loop, out_dir
        self.on = False
        self.t0 = math.inf
        self.prefill_calls: list[tuple[float, int]] = []
        self.decode_calls: list[tuple[float, list[int]]] = []
        self._window = None
        pre, dec, write = loop.prefill, loop._decode_arena, loop._write_slot

        def prefill(p, toks):
            if self.on:
                self.prefill_calls.append((loop.now(), int(toks.shape[1])))
            with self.span("chipbench.prefill"):
                return pre(p, toks)

        def decode(p, arena, toks, act):
            if self.on:
                by_id = loop._by_id
                valid = [len(by_id[rid].prompt) + len(by_id[rid].tokens)
                         for rid in loop._slot_rid if rid is not None]
                self.decode_calls.append((loop.now(), valid))
            with self.span("chipbench.decode"):
                return dec(p, arena, toks, act)

        def slot_write(arena, one, slot):
            with self.span("chipbench.slot_write"):
                return write(arena, one, slot)

        loop.prefill, loop._decode_arena, loop._write_slot = prefill, decode, slot_write

    def span(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        self.jax.profiler.start_trace(self.dir)
        self._window = self.span("chipbench.window")
        self._window.__enter__()
        self.t0 = self.loop.now()
        self.on = True

    def stop(self) -> None:
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
        self.on = False


def main(argv=None, root: Path = spec.ROOT, chip_check=require_chip) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the profiler's trace in (default: deleted)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)
    c, mix, cs = cell.config, cell.mix, cell.spec

    import jax

    dev = chip_check(jax, cs["chips"])
    from chipbench import check, driver, e2e, flops, trace
    from chipbench.system import build_loop, enable_compile_cache
    from chipbench.traffic import Traffic, prompt_buckets
    from chipbench.weights import make_weights

    peak = flops.peaks(dev["kind"]) if args.trace else None
    enable_compile_cache()
    # cache every program, however fast it compiles, so a warm set-up
    # loads the same set every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.__setitem__(0, compiles[0] + (event == COMPILE_EVENT)))

    batch, max_len = cs["arena"]["batch"], cs["arena"]["max_len"]
    params = jax.block_until_ready(make_weights(c, args.seed))
    loop = build_loop(c, params, batch, max_len)
    del params
    warm(loop, prompt_buckets(mix))
    traffic = Traffic(mix, c["vocab_size"], args.seed, rate=cs.get("rate_per_s"), seconds=args.seconds)

    tracer = at = None
    if args.trace:
        trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="chipbench-trace-")
        tracer = Tracer(jax, loop, trace_dir)
        at = (max(0.0, args.seconds - mix["trace_seconds"]), tracer.start)
    setup_s = time.perf_counter() - T_START

    before = compiles[0]
    records = driver.drive(loop, traffic, args.seconds, clients=cs.get("clients"),
                           span=tracer.span if tracer else driver.null_span, at=at)
    in_window = compiles[0] - before
    if tracer:
        tracer.stop()
    stats = loop.stats()
    device = dict(dev, memory_peak_bytes=max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()))
    lateness = [r.sent - r.due for r in records] or [0.0]
    slowest = stats["slowest_tick"] or {"s": 0.0, "at_s": 0.0}
    print(f"window: {len(records)} requests sent, {stats['completed']} completed, "
          f"{stats['decode_calls']} decode calls, occupancy {stats['slot_occupancy']:.3f}, "
          f"generator late by at most {max(lateness) * 1e3:.1f} ms, slowest tick "
          f"{slowest['s'] * 1e3:.1f} ms at {slowest['at_s']:.1f} s; "
          f"compiles in the window: {in_window}", file=sys.stderr)

    close = args.seconds
    metrics = {}
    breakdown = None
    wanted = spec.reported("per_layer" if args.trace else "end_to_end", args.workload, root)
    if args.trace:
        tr = trace.load(trace.find_xplane(tracer.dir))
        ctx = Context(c, records, close, peak, tr,
                      [n for t, n in tracer.prefill_calls if t >= tracer.t0],
                      [v for t, v in tracer.decode_calls if t >= tracer.t0],
                      stats, trace.program_spans(tr))
        for name, mod in spec.load_metrics(root).items():
            value = mod.read(ctx) if wanted is None or name in wanted else None
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        device.update(busy_s=trace.busy_s(tr), window_s=tr.window_s)
        breakdown = trace.breakdown(tr)
        if not args.keep_trace:
            shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        measured = dict(e2e.end_to_end(records, close), setup_s=(setup_s, "s", 1))
        for name, (value, unit, n) in measured.items():
            print(f"{name}: {value:.4f} {unit} over {n} samples", file=sys.stderr)
            if wanted is None or name in wanted:
                metrics[name] = {"value": value, "unit": unit}

    # the reference runs on a chip the program has let go of
    del loop, tracer, at
    gc.collect()
    lim = cs["limits"]
    picked = check.sample(records, args.seed, mix["sample"]["min_tokens"], mix["sample"]["max_requests"])
    t_ref = time.perf_counter()
    gap = check.gaps(c, args.seed, picked, max_len) if picked else None  # null: nothing to compare
    print(f"reference: {len(picked)} requests in {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    n_served = sum(len(r.req.tokens) for r in picked)
    short = sum(1 for r in records if r.req.finished >= 0 and len(r.req.tokens) != r.req.max_new)
    checks = {
        "max_logit_gap_at_most": {"value": gap, "limit": lim["max_logit_gap"]},
        "sampled_tokens_at_least": {"value": n_served, "limit": mix["sample"]["min_tokens"]},
        "wrong_length_at_most": {"value": short, "limit": 0},
    }
    correct = (gap is not None and gap <= lim["max_logit_gap"] and n_served >= mix["sample"]["min_tokens"]
               and short == 0 and stats["rejected"] == 0)
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": stats["rejected"] + short, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    try:
        main()
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        sys.exit(2)
