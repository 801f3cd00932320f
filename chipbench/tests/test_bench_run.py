"""The open and closed loop drivers complete a smoke-size ServeLoop
window through ``run.main``, with the chip check stood in for."""

import json

import jax
import pytest

import chipbench.system as system
from chipbench import e2e, run
from chipbench.tests import smoke


@pytest.fixture
def root(tmp_path, monkeypatch):
    # no persistent cache on the CPU, and run.main's cache setting undone
    monkeypatch.setattr(system, "enable_compile_cache", lambda: None)
    saved = jax.config.jax_persistent_cache_min_compile_time_secs
    yield smoke.make_root(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved)


def _run(root, cell, trace=0, seconds=4.0, seed=2**31 + 5):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                     "--trace", str(trace)], root=root, chip_check=smoke.cpu_chip)


@pytest.mark.parametrize("cell", ["smoke.chat", "smoke.long-prompt"])
def test_window_completes_and_checks_correct(root, cell, capsys):
    res = _run(root, cell)
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1]) == json.loads(json.dumps(res))
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "check"  # the compared numbers come last
    assert "compiles in the window: 0" in out.err
    assert out.err.strip().splitlines()[-1].startswith("check ")


def test_driver_records(root):
    """Every token of a finished request is stamped, in order, the first at
    the program's own first-token stamp; closed-loop clients stay busy."""
    from chipbench import driver, spec
    from chipbench.traffic import Traffic, prompt_buckets
    from chipbench.weights import make_weights

    cell = spec.load_cell("smoke.long-prompt", root)
    c = cell.config
    loop = system.build_loop(c, make_weights(c, 3), 4, 256)
    run.warm(loop, prompt_buckets(cell.mix))
    recs = driver.drive(loop, Traffic(cell.mix, c["vocab_size"], 3), 1.5, clients=4)
    done = [r for r in recs if r.req.finished >= 0]
    assert len(done) >= 4 and len(recs) == len(done) + 4  # four clients, one request each open
    for r in done:
        assert len(r.stamps) == len(r.req.tokens) == r.req.max_new
        assert r.stamps[0] == r.req.first_token and r.stamps == sorted(r.stamps)
        assert r.req.arrived == r.due <= r.req.submitted <= r.req.first_token
    assert e2e.tokens_per_s(recs, 1.5) > 0


def test_traced_run_on_cpu_reports_host_metrics_only(root):
    """A traced run on the CPU: the trace holds no TPU device, so the
    readers that need one return nothing and are left out of the line."""
    res = _run(root, "smoke.chat", trace=1)
    assert set(res["metrics"]) == {"sched.queue_wait_p90_ms"}
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0.0
