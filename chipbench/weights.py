"""Random weights from ``--seed``, made by the benchmark on the device.

One jitted call makes every array, in float32 (the type the program serves
its parameters in), laid out as the program's parameter tree: the
program takes them as input and the reference makes them again, from the
same seed, after the program's state is freed. So the reference takes no
weights that the program made.

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

EMBED_STD = 0.02
NORM_STD = 0.1


def dims(c: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    return {
        "d": c["hidden_size"], "L": c["num_hidden_layers"], "H": c["num_attention_heads"],
        "KH": c["num_key_value_heads"], "hd": c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        "ff": c["intermediate_size"], "V": c["vocab_size"],
    }


def layout(c: dict) -> dict:
    """``{path: (shape, std, fan_in_axes)}`` of every parameter, where
    ``fan_in_axes`` are the contracted axes (None for an embedding or gain)."""
    z = dims(c)
    d, L, H, KH, hd, ff, V = (z[k] for k in ("d", "L", "H", "KH", "hd", "ff", "V"))
    out = {
        ("embed",): ((V, d), EMBED_STD, None),
        ("final_norm",): ((d,), NORM_STD, None),
        ("layers", "b0", "norm"): ((L, d), NORM_STD, None),
        ("layers", "b0", "attn", "wq"): ((L, d, H, hd), None, (1,)),
        ("layers", "b0", "attn", "wk"): ((L, d, KH, hd), None, (1,)),
        ("layers", "b0", "attn", "wv"): ((L, d, KH, hd), None, (1,)),
        ("layers", "b0", "attn", "wo"): ((L, H, hd, d), None, (1, 2)),
        ("layers", "b0", "ffn_norm"): ((L, d), NORM_STD, None),
        ("layers", "b0", "ffn", "gate"): ((L, d, ff), None, (1,)),
        ("layers", "b0", "ffn", "up"): ((L, d, ff), None, (1,)),
        ("layers", "b0", "ffn", "down"): ((L, ff, d), None, (1,)),
    }
    if c["qk_norm"]:
        out[("layers", "b0", "attn", "q_norm")] = ((L, hd), NORM_STD, None)
        out[("layers", "b0", "attn", "k_norm")] = ((L, hd), NORM_STD, None)
    if not c["tie_word_embeddings"]:
        out[("lm_head",)] = ((d, V), None, (0,))
    return out


def param_count(c: dict) -> int:
    return sum(math.prod(shape) for shape, _, _ in layout(c).values())


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: JAX keeps only 32 bits of a seed, so
    the high word is folded in."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _make(c: dict, key: jax.Array) -> dict:
    tree: dict = {}
    items = sorted(layout(c).items())
    for k, (path, (shape, std, fan_in)) in zip(jax.random.split(key, len(items)), items):
        if std is None:
            std = 1.0 / math.sqrt(math.prod(shape[a] for a in fan_in))
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = std * jax.random.normal(k, shape, jnp.float32)
    return tree


@functools.lru_cache(maxsize=None)
def maker(config_json: str):
    """The jitted weight maker of one configuration (a JSON string, so the
    cache key is the file's content)."""
    return jax.jit(functools.partial(_make, json.loads(config_json)))


def make_weights(c: dict, seed: int) -> dict:
    """All weights of configuration ``c`` from ``seed``, in one jitted call."""
    return maker(json.dumps(c, sort_keys=True))(seed_key(seed))
