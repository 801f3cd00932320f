"""Readings that set a cell's correctness limit: the program's widest gap
and the float8 control's, on many seeds, in one process.

  python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20

Per seed: weights from the seed, one window of the cell's own traffic and
load, then the sample ``run.py`` would check, read by the float32
reference (the served tokens' widest gap, the program's reading) and by
the reference computed in float8 (the widest gap of the tokens that pass
ranks first, the control's reading). The limit lies between the largest
program reading and the smallest control reading.
"""

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import check, driver, run, spec  # noqa: E402


class Calibration:
    """One replica reused across seeds: each seed brings its own weights."""

    def __init__(self, cell: spec.Cell):
        self.cell, self.loop = cell, None

    def read(self, seed: int, seconds: float) -> dict:
        from chipbench.system import build_loop, serving_params
        from chipbench.traffic import Traffic, prompt_buckets
        from chipbench.weights import make_weights

        c, cs, mix = self.cell.config, self.cell.spec, self.cell.mix
        max_len = cs["arena"]["max_len"]
        params = make_weights(c, seed)
        if self.loop is None:
            self.loop = build_loop(c, params, cs["arena"]["batch"], max_len)
            run.warm(self.loop, prompt_buckets(mix))
        else:  # the tree the cells' programs read, as ServeLoop casts it when built
            self.loop.params = serving_params(self.loop.cfg, params)
        del params
        traffic = Traffic(mix, c["vocab_size"], seed, rate=cs.get("rate_per_s"), seconds=seconds)
        recs = driver.drive(self.loop, traffic, seconds, clients=cs.get("clients"))
        self.loop.params, self.loop._arena = None, None  # the reference's weights need the room
        gc.collect()
        picked = check.sample(recs, seed, mix["sample"]["min_tokens"], mix["sample"]["max_requests"])
        served, low = check.gaps(c, seed, picked, max_len, control=True)
        return {"seed": seed, "program_gap": served, "control_gap": low,
                "sampled_requests": len(picked),
                "sampled_tokens": sum(len(r.req.tokens) for r in picked)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax

    dev = run.require_chip(jax, cell.spec["chips"])
    from chipbench.system import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cal = Calibration(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(dict(cal.read(seed, args.seconds), device=dev["kind"])), flush=True)


if __name__ == "__main__":
    main()
