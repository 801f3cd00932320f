"""Random weights from ``--seed``, made by the benchmark on the device.

One jitted call makes every array, in the configuration's
``param_dtype``, laid out as the program's parameter tree (the
architecture module's ``layout``): the program takes them as input and
the reference makes them again, from the same seed, after the program's
state is freed. So the reference takes no weights that the program made.
Each leaf is drawn directly in that dtype, so a bfloat16 tree needs no
float32 copy, and a float32 tree is what it always was.

Scales: matrices draw N(0, 1/fan_in) over their contracted dimensions, the
embedding N(0, 0.02²) (the source's ``initializer_range``), and RMSNorm
gains ``g`` N(0, 0.1²) in the program's ``x̂ · (1 + g)`` convention, so the
gains are not all one and a norm applied to the wrong axis shows.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from chipbench import spec

EMBED_STD = 0.02
NORM_STD = 0.1


def param_count(c: dict) -> int:
    return spec.arch(c).param_count(c)


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: JAX keeps only 32 bits of a seed, so
    the high word is folded in."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _make(c: dict, key: jax.Array) -> dict:
    tree: dict = {}
    dtype = jnp.dtype(c["param_dtype"])
    items = sorted(spec.arch(c).layout(c).items())
    for k, (path, (shape, std, fan_in)) in zip(jax.random.split(key, len(items)), items):
        if std is None:
            std = 1.0 / math.sqrt(math.prod(shape[a] for a in fan_in))
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = std * jax.random.normal(k, shape, dtype)
    return tree


@functools.lru_cache(maxsize=None)
def maker(config_json: str):
    """The jitted weight maker of one configuration (a JSON string, so the
    cache key is the file's content)."""
    return jax.jit(functools.partial(_make, json.loads(config_json)))


def make_weights(c: dict, seed: int) -> dict:
    """All weights of configuration ``c`` from ``seed``, in one jitted call."""
    return maker(json.dumps(c, sort_keys=True))(seed_key(seed))
