"""Multi-device tests (8 placeholder host devices via subprocess — the
XLA device count must be set before jax initializes, so these run in
spawned interpreters). Sharded steps run under ``jax.set_mesh``, the
context ``parallel/sharding._active_mesh`` reads."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # JAX-compile-heavy: deselected in the default tier-1 run

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_flash_decode_matches_ref():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.parallel.flash_decode import sharded_decode_attention
        from repro.kernels import ref
        mesh = make_mesh((2, 4))
        rng = np.random.default_rng(0)
        B,S,H,KH,D = 4, 256, 8, 2, 64
        q = jnp.asarray(rng.standard_normal((B,H,D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B,S,KH,D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B,S,KH,D)), jnp.float32)
        valid = jnp.asarray(rng.random((B,S)) > 0.2)
        out = sharded_decode_attention(q, k, v, valid, mesh, use_kernel=True, interpret=True)
        exp = ref.decode_attention_ref(q, k, v, valid)
        err = float(jnp.abs(out-exp).max())
        assert err < 1e-5, err
        print("ok", err)
    """))


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 2×4 mesh must equal the unsharded step."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import RunConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_step
        from repro.models import model as M
        from repro.optim import adamw
        from repro.parallel.sharding import rules_from_mesh

        cfg = get_config("internlm2-1.8b").reduced(
            num_layers=2, d_model=64, vocab_size=64,
            param_dtype="float32", compute_dtype="float32")
        run = RunConfig(remat="none", attention_impl="chunked", attention_chunk=16, z_loss=0.0)
        params = M.init_model(jax.random.PRNGKey(0), cfg)
        opt = adamw.init_opt_state(params)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 64),
            "mask": jnp.ones((8, 32), jnp.float32),
        }
        # single-device reference
        p1, o1, m1 = jax.jit(make_train_step(cfg, run, None))(params, opt, batch)

        mesh = make_mesh((2, 4))
        rules = rules_from_mesh(mesh)
        pspecs = M.model_specs(cfg, rules)
        with jax.set_mesh(mesh):
            step = jax.jit(make_train_step(cfg, run, rules))
            p2, o2, m2 = step(params, opt, batch)
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        assert dl < 1e-4, dl
        errs = [float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(p1), jax.tree.leaves(p2))]
        assert max(errs) < 1e-4, max(errs)
        print("ok loss_delta", dl, "max_param_err", max(errs))
    """))


def test_dryrun_cli_smoke_cell(tmp_path):
    """The dry-run CLI end to end on a tiny mesh with a reduced arch.

    Artifacts go to pytest's tmp dir, NOT results/: a test must never
    dirty the working tree (results/ is generated output and gitignored —
    this test once wrote results/dryrun_test/ and left churn in every
    run's diff)."""
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = str(REPO / "src")
    out_dir = tmp_path / "dryrun_test"
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--cell", "qwen3-1.7b-smoke:train_4k", "--mesh", "2x4",
         "--out", str(out_dir), "--attention-chunk", "512"],
        capture_output=True, text=True, timeout=480, env=env, cwd=REPO,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    rec = json.loads((out_dir / "qwen3-1.7b-smoke__train_4k__2x4.json").read_text())
    assert rec["ok"]
    assert rec["hlo_flops_per_dev"] > 0
    assert rec["t_compute"] > 0 and rec["t_memory"] > 0
    assert rec["dominant"] in ("compute", "memory", "collective")
    assert 0 < rec["useful_flop_ratio"] < 2.0
