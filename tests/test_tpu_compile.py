"""Compile the served path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (block layouts not aligned to the tiling, kernels over the fast
memory, programs over the device's memory). The topology is described in
a module-scoped fixture, never at import: only one process may load the
TPU library, and the test workers all import this file. The persistent
compilation cache is off around these compiles, since a TPU entry written
here cannot be read back without a chip.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.kernels import ops
from repro.launch.serve import ServeLoop
from repro.models import model as M

QWEN3 = get_config("qwen3-1.7b")
BATCH = 8  # the served arena width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize(
    "max_len,partials",
    [(1024, False), (1024, True), (1000, False), (1000, True)],
    ids=["1024-normalized", "1024-partials", "1000-normalized", "1000-partials"],
)
def test_decode_kernel_compiles_for_v5e(one_chip, max_len, partials):
    """The flash-decode kernel at Qwen3 widths (8 KV heads of 128, two q
    heads each) over the served arena; 1000 is a cache length the block
    does not divide."""
    h, kh, d = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim_
    kv = _on(one_chip, (BATCH, max_len, kh, d), jnp.bfloat16)
    _, text = _compile(
        lambda q, k, v, valid: ops.decode_attention(q, k, v, valid, return_partials=partials),
        _on(one_chip, (BATCH, h, d), jnp.bfloat16), kv, kv,
        _on(one_chip, (BATCH, max_len), jnp.bool_),
    )
    assert "tpu_custom_call" in text


def test_flash_attention_forward_compiles_for_v5e(one_chip):
    """The prefill kernel's forward at Qwen3 widths."""
    h, kh, d = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.head_dim_
    s = 1024
    kv = _on(one_chip, (1, s, kh, d), jnp.bfloat16)
    _, text = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v),
        _on(one_chip, (1, s, h, d), jnp.bfloat16), kv, kv,
    )
    assert "tpu_custom_call" in text


def test_arena_decode_step_compiles_and_fits_one_v5e(one_chip):
    """The served decode step at full width: the fused decode+argmax of
    ``ServeLoop``'s arena over batch 8 × max_len 1024, with the kernel
    (the platform's choice on a TPU) inside, within one chip's 16 GB."""
    run = RunConfig(remat="none", attention_impl="xla", decode_attention_impl="kernel")
    put = lambda tree: jax.tree.map(lambda x: _on(one_chip, x.shape, x.dtype), tree)
    # the tree ServeLoop serves: matrices in bf16, cast when it is built
    params = put(jax.eval_shape(lambda p: M.serving_params(QWEN3, p), M.model_shapes(QWEN3)))
    arena = put(jax.eval_shape(lambda: M.init_cache(QWEN3, BATCH, 1024)))

    def arena_decode(p, c, toks, act):
        logits, cache = M.decode_step(QWEN3, run, p, c, toks, None, active=act)
        return jnp.argmax(logits[:, -1, :], axis=-1), cache

    compiled, text = _compile(
        arena_decode, params, arena,
        _on(one_chip, (BATCH, 1), jnp.int32), _on(one_chip, (BATCH,), jnp.bool_),
    )
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, used


# an instruction whose result is the stacked cache or one layer of it in the
# arena's (B, S, KH, D) order, at the chat cell's width (16 x 1024)
ARENA_SHAPED = re.compile(r"%([\w.\-]+) = \(?bf16\[(?:28,|1,)?16,1024,8,128\]\S* ([\w\-]+)\(")


def test_served_arena_programs_update_it_in_place_on_v5e(one_chip):
    """``ServeLoop``'s decode step and slot write at the chat cell's width:
    each aliases the whole arena from input to output, and the step holds
    no copy and no masked select over the stacked cache or a layer of it,
    which is what the step spent its arena time on before it was donated."""
    run = RunConfig(remat="none", attention_impl="xla", decode_attention_impl="kernel")
    batch, max_len = 16, 1024
    loop = ServeLoop(QWEN3, run, None, batch=batch, max_len=max_len, mode="arena")
    put = lambda tree: jax.tree.map(lambda x: _on(one_chip, x.shape, x.dtype), tree)
    params = put(jax.eval_shape(lambda p: M.serving_params(QWEN3, p), M.model_shapes(QWEN3)))
    arena = put(jax.eval_shape(lambda: M.init_cache(QWEN3, batch, max_len)))
    one = put(jax.eval_shape(lambda: M.init_cache(QWEN3, 1, max_len)))
    step = loop._decode_arena.lower(
        params, arena, _on(one_chip, (batch, 1), jnp.int32), _on(one_chip, (batch,), jnp.bool_)
    ).compile()
    write = loop._write_slot.lower(arena, one, _on(one_chip, (), jnp.int32)).compile()
    arena_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(arena))
    for compiled in (step, write):  # device tiling pads the (16,) positions
        assert compiled.memory_analysis().alias_size_in_bytes >= arena_bytes
    found = [(name, op) for name, op in ARENA_SHAPED.findall(step.as_text())
             if op in ("copy", "select") or (op == "fusion" and re.search("copy|select", name))]
    assert not found, found
