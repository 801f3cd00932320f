"""Find a chat cell's knee once: the same cell at several offered rates,
one set-up, one window per rate, in one process.

  python chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,2.5,3

Per rate it prints the requests due and finished in the window, the
backlog at the close (due, not finished), tokens/s and the tails. The
knee is the highest rate whose backlog is at most one arena batch and
whose TTFT tail has not run away from its value at low rates (a queue
that grows through the window); the cell's fixed rate is about 0.8 of it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import driver, e2e, run, spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    c, cs = cell.config, cell.spec
    import jax

    dev = run.require_chip(jax, cs["chips"])
    from chipbench.system import build_loop, enable_compile_cache
    from chipbench.traffic import Traffic, prompt_buckets
    from chipbench.weights import make_weights

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    loop = build_loop(c, make_weights(c, args.seed), cs["arena"]["batch"], cs["arena"]["max_len"])
    run.warm(loop, prompt_buckets(cell.mix))
    close = args.seconds
    for rate in (float(x) for x in args.rates.split(",")):
        traffic = Traffic(cell.mix, c["vocab_size"], args.seed, rate=rate, seconds=close)
        t = time.perf_counter()
        recs = driver.drive(loop, traffic, close)
        done = sum(1 for r in recs if 0 <= r.req.finished <= close)
        row = {"rate_per_s": rate, "due": len(recs), "finished": done,
               "backlog": len(recs) - done,
               **{k: v[0] for k, v in e2e.end_to_end(recs, close).items()},
               "wall_s": time.perf_counter() - t, "device": dev["kind"]}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
