"""Compile each cell's programs for a described TPU v5e and print what
each holds in device memory. Nothing runs; no chip is needed.

  JAX_PLATFORMS=cpu python chipbench/rehearse.py [cell ...]

For every cell (default: all): the arena decode step at the cell's batch
and length with the Pallas decode kernel, the prefill of its longest
prompt bucket and the slot write, all on the tree ``ServeLoop`` serves
(``serving_params`` of the weights' shapes); the weight maker, with the
largest leaf it makes; and the reference's gap program on the weights as
made. ``memory_analysis`` counts one program at a time; the decode step's
arguments are the served parameters and the arena.
"""

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import reference, spec  # noqa: E402
from chipbench.system import ServeLoop, model_config, serving_params  # noqa: E402
from chipbench.traffic import prompt_buckets  # noqa: E402
from chipbench.weights import _make, maker  # noqa: E402


def main(names) -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    from repro.configs.base import RunConfig
    from repro.models import model as M

    for name in names:
        cell = spec.load_cell(name)
        c, cs = cell.config, cell.spec
        batch, max_len = cs["arena"]["batch"], cs["arena"]["max_len"]
        cfg = model_config(c)
        run = RunConfig(remat="none", attention_impl="xla", decode_attention_impl="kernel")
        loop = ServeLoop(cfg, run, None, batch=batch, max_len=max_len, mode="arena")
        made = on(jax.eval_shape(lambda: _make(c, jax.random.PRNGKey(0))))
        params = on(jax.eval_shape(lambda p: serving_params(cfg, p), made))
        largest = max(x.size * x.dtype.itemsize for x in jax.tree.leaves(made))
        arena = on(jax.eval_shape(lambda: M.init_cache(cfg, batch, max_len)))
        longest = max(prompt_buckets(cell.mix))
        one = on(jax.eval_shape(lambda: M.init_cache(cfg, 1, max_len)))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
        progs = {
            "decode_step": loop._decode_arena.lower(
                params, arena, i32(batch, 1), jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=chip)),
            f"prefill_{longest}": loop.prefill.lower(params, i32(1, longest)),
            "slot_write": loop._write_slot.lower(arena, one, i32()),
            "weights": maker(json.dumps(c, sort_keys=True)).lower(
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)),
            "reference": reference.gap_fn(json.dumps(c, sort_keys=True), False).lower(
                made, i32(max_len), i32(max_len)),
        }
        for prog, lowered in progs.items():
            compiled = lowered.compile()
            m = compiled.memory_analysis()
            kernel = "tpu_custom_call" in compiled.as_text() if prog == "decode_step" else None
            print(f"{name} {prog}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
                  f"outputs {m.output_size_in_bytes / 1e9:.3f} GB, temporaries "
                  f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased {m.alias_size_in_bytes / 1e9:.3f} GB"
                  + ("" if kernel is None else f", Pallas kernel in the step: {kernel}")
                  + (f", largest leaf {largest / 1e9:.3f} GB" if prog == "weights" else ""), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or sorted(p.stem for p in (spec.ROOT / "cells").glob("*.json")))
