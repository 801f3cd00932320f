"""JAX's persistent compilation cache for the entry points.

Each entry point (``serve.main``, ``fleet.main``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile. Nothing calls it at
import, so tests and library users keep JAX's own defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (gitignored). The directory is fixed, never built
# from a temp name, a pid or the time: a cache that moves never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, stays in charge: JAX reads it
    itself and no other directory is set here. Otherwise the cache lives
    in the checkout's fixed ``.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
