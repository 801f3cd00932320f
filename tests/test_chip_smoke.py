"""chip_smoke.py off the chip: its phases at smoke width on the CPU (the
decode kernel in interpret mode), the fleet phase over four virtual
devices, and its refusal to run, or to print a result, without a TPU."""

import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.configs import get_config
from repro.models import model as M

REPO = Path(__file__).resolve().parents[1]
SMOKE = dict(batch=4, max_len=64, prompt_lens=(12, 16), gen=6, seed=0)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_one_chip_phases_at_smoke_width():
    cs = _chip_smoke()
    cfg = get_config(cs.ARCH).reduced()
    run = dataclasses.replace(cs.serve_run_config(), decode_attention_impl="kernel_interpret")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    cs.check_kernel_vs_einsum(cfg, SMOKE["max_len"], SMOKE["batch"], 0, interpret=True)
    cs.check_decode_matches_forward(cfg, run, params, SMOKE["max_len"], 0)
    stats = cs.serve_phase(cfg, run, params, n_requests=8, **SMOKE)
    assert stats["completed"] == 8
    assert stats["decode_calls"] < stats["decode_steps"]


def test_fleet_phase_puts_each_replica_on_its_own_device():
    code = textwrap.dedent(
        f"""
        import dataclasses, importlib.util, jax
        spec = importlib.util.spec_from_file_location("chip_smoke", {str(REPO / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_config
        from repro.models import model as M
        assert len(jax.devices()) == 4
        cfg = get_config(cs.ARCH).reduced()
        run = dataclasses.replace(cs.serve_run_config(), decode_attention_impl="kernel_interpret")
        params = M.init_model(jax.random.PRNGKey(0), cfg)
        stats = cs.fleet_phase(cfg, run, params, n_replicas=4, batch=2, max_len=64,
                               prompt_lens=(12, 16), gen=6, seed=0)
        print("completed", stats["completed"])
        """
    )
    env = _env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "completed 8" in out.stdout


def test_refuses_without_a_tpu():
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")], env=_env(),
                         capture_output=True, text=True, timeout=300, cwd=REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
