"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: enabling the cache changes JAX's
process-wide configuration, which must not leak into other tests."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

PROBE = textwrap.dedent(
    """
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    where = enable_compile_cache()
    print(where)
    print(jax.config.jax_compilation_cache_dir)
    if {compile}:
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    """
)


def _run(env_dir, compile_: bool) -> list[str]:
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", PROBE.format(compile=compile_)],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_dir_stays_in_charge(tmp_path):
    cache = tmp_path / "jax-cache"
    where, configured = _run(cache, compile_=True)
    assert where == configured == str(cache)
    assert any(cache.iterdir()), "nothing was cached in JAX_COMPILATION_CACHE_DIR"


def test_default_dir_is_fixed_in_the_checkout():
    where, configured = _run(None, compile_=False)
    assert where == configured == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
