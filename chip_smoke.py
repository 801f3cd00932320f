#!/usr/bin/env python3
"""Smoke check of the serving path on a TPU: full-width qwen3-1.7b.

One chip (the default) runs, in one process:

  1. the Pallas flash-decode kernel against the einsum reference at the
     served attention shapes (batch 8, max_len 1024, 8 KV heads of 128);
  2. prefill + incremental decode against ``M.forward`` over the whole
     sequence, with the platform's own decode path (the kernel on a TPU);
  3. 16 requests of two prompt lengths through ``ServeLoop`` in arena mode
     behind the admission door, 32 new tokens each.

``--chips 4`` runs only the fleet phase: ``FleetLoop`` over four full-width
replicas, one per chip, under ``capacity_weighted`` routing, then the same
prefill+decode probe on every chip, compared with chip 0's logits.

The weights are random, made from ``--seed``. Every check raises on
failure, so the process exits nonzero and prints no result line; the last
line of stdout is ``{"ok": true, "device": {...}}`` only when every phase
passed. The times printed are smoke timings of one cold run, not benchmark
numbers. Without a TPU the script fails at once: it never sets
``JAX_PLATFORMS`` and never carries on on the CPU.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the fleet phase on four chips
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.data.dataset import SyntheticCorpus  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.fleet import build_fleet  # noqa: E402
from repro.launch.serve import Request, ServeLoop  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.attention import resolve_decode_impl  # noqa: E402

ARCH = "qwen3-1.7b"
BATCH = 8
MAX_LEN = 1024
PROMPT_LENS = (96, 128)
GEN = 32
N_REQUESTS = 16

# Tolerances, fixed before the first chip run.
# Decode vs forward: ||decode - forward|| / ||forward|| over the compared
# logits. Two bf16 evaluation orders (whole-sequence attention vs cache +
# kernel) through 28 layers: on the CPU, 28 layers at d_model 512-1024 with
# random weights gave 3.9e-2 in bf16 and 6e-6 in float32, while decoding
# one position off gave 1.3-1.4.
DECODE_VS_FORWARD_RTOL = 0.15
# Kernel vs einsum: bf16 attention outputs (|values| up to ~3, bf16 ulp
# there ~1.6e-2) against an f32 reference at "highest" matmul precision.
KERNEL_VS_EINSUM_ATOL = 2e-2
# Fleet probe: the same program on chips of one kind; only a wrong
# placement or a broken transfer moves it.
FLEET_PROBE_RTOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def serve_run_config() -> RunConfig:
    """The serving run config of ``launch/serve.main``: prefill on the XLA
    attention, decode attention chosen from the platform."""
    return RunConfig(remat="none", attention_impl="xla")


def make_requests(cfg, n: int, prompt_lens, gen: int, seed: int) -> list[Request]:
    corpus = SyntheticCorpus(cfg.vocab_size, max(prompt_lens), seed)
    return [
        Request(i, corpus.grain_tokens(i, 1)[0][: prompt_lens[i % len(prompt_lens)]], gen)
        for i in range(n)
    ]


def probe_fn(cfg, run, split: int, max_len: int):
    """Jitted prefill of ``tokens[:, :split]``, then one decode step per
    remaining token; returns logits (B, S - split + 1, V) that line up with
    ``M.forward``'s logits at positions ``split - 1 .. S - 1``."""

    def probe(params, tokens):
        pre, cache = M.prefill(cfg, run, params, tokens[:, :split], max_len)

        def step(cache, tok):
            logits, cache = M.decode_step(cfg, run, params, cache, tok[:, None])
            return cache, logits[:, 0]

        _, dec = jax.lax.scan(step, cache, tokens[:, split:].T)
        return jnp.concatenate([pre, dec.transpose(1, 0, 2)], axis=1)

    return jax.jit(probe)


def check_kernel_vs_einsum(cfg, max_len: int, batch: int, seed: int, interpret: bool = False) -> None:
    """The decode kernel against the einsum reference at the served shapes:
    rows filled to different depths, one full row, one parked (empty) row."""
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (batch, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (batch, max_len, kh, d), jnp.bfloat16)
    v = jax.random.normal(kv, (batch, max_len, kh, d), jnp.bfloat16)
    # one row per depth from a single key to the full cache, then a parked
    # slot with no valid key
    fill = np.append(np.linspace(1, max_len, batch - 1).astype(np.int32), 0)
    valid = np.arange(max_len)[None, :] < fill[:, None]

    out = jax.jit(lambda *a: ops.decode_attention(*a, interpret=interpret))(q, k, v, valid)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.decode_attention_ref)(q.astype(jnp.float32), k, v, valid)
    out, want = np.asarray(out, np.float32), np.asarray(want)
    live = fill > 0
    err = float(np.abs(out[live] - want[live]).max())
    print(f"check kernel-vs-einsum: shapes q{tuple(q.shape)} kv{tuple(k.shape)} "
          f"fills {fill.tolist()}: max|err| {err:.3e} (tol {KERNEL_VS_EINSUM_ATOL:g})")
    check(err <= KERNEL_VS_EINSUM_ATOL, f"decode kernel diverges from einsum: {err}")
    check(bool(np.isfinite(out).all()), "decode kernel produced a non-finite value")
    check(float(np.abs(out[~live]).max()) == 0.0, "parked row is not exactly zero")


def check_decode_matches_forward(cfg, run, params, max_len: int, seed: int) -> None:
    """Prefill + per-token decode reproduce the whole-sequence forward."""
    b, s, split = 2, 64, 56
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, s), 0, cfg.vocab_size)
    full = jax.jit(lambda p, t: M.forward(cfg, run, p, t)[0])(params, tokens)
    got = probe_fn(cfg, run, split, max_len)(params, tokens)
    want = np.asarray(full[:, split - 1:], np.float32)
    got = np.asarray(got, np.float32)
    check(got.shape == want.shape, f"decode logits shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "decode produced a non-finite logit")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    rel_max = float(np.abs(got - want).max() / np.abs(want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    print(f"check decode-vs-forward: B={b} prompt {split} + {s - split} decode steps, "
          f"max_len {max_len}: ||err||/||ref|| {rel:.3e} (tol {DECODE_VS_FORWARD_RTOL:g}), "
          f"max|err|/max|ref| {rel_max:.3e}, argmax agreement {agree:.3f}")
    check(rel <= DECODE_VS_FORWARD_RTOL, f"decode diverges from forward: {rel}")


def _check_served(reqs: list[Request], stats: dict, n: int, gen: int) -> None:
    check(stats["completed"] == n, f"completed {stats['completed']} of {n}")
    check(stats["rejected"] == 0, f"{stats['rejected']} requests rejected")
    short = [r.rid for r in reqs if len(r.tokens) != gen]
    check(not short, f"requests without {gen} tokens: {short}")


def serve_phase(cfg, run, params, *, batch: int, max_len: int, prompt_lens, gen: int,
                n_requests: int, seed: int) -> dict:
    """The replica's main path: ``ServeLoop`` arena mode, one admission door."""
    reqs = make_requests(cfg, n_requests, prompt_lens, gen, seed)
    loop = ServeLoop(cfg, run, params, batch=batch, max_len=max_len, mode="arena")
    t = time.perf_counter()
    for n in prompt_lens:  # every shape compiles before the session opens
        loop.warm(n)
    compile_s = time.perf_counter() - t
    prompt = np.asarray(reqs[-1].prompt[None], np.int32)
    t = time.perf_counter()
    jax.block_until_ready(loop.prefill(loop.params, prompt))
    prefill_s = time.perf_counter() - t

    stats = loop.run_requests(reqs)
    _check_served(reqs, stats, n_requests, gen)
    check(stats["decode_calls"] < stats["decode_steps"],
          f"no batching: {stats['decode_calls']} calls for {stats['decode_steps']} steps")
    check(stats["arena_in_place"] == 1.0,
          f"the arena was not donated to every call: {stats['arena_in_place']}")
    if resolve_decode_impl(run.decode_attention_impl) == "kernel":
        # the served decode step itself must carry the kernel
        hlo = loop._decode_arena.lower(
            loop.params, loop._arena, np.zeros((batch, 1), np.int32), np.ones(batch, bool)
        ).as_text()
        check("tpu_custom_call" in hlo, "the arena decode step does not call the kernel")
        print("check served decode step: calls the Pallas kernel (tpu_custom_call)")
    print(f"serve: {stats['completed']}/{n_requests} requests, {stats['decode_steps']} decode "
          f"steps in {stats['decode_calls']} calls, occupancy {stats['slot_occupancy']:.3f}, "
          f"arena in place {stats['arena_in_place']:.2f}")
    print(f"smoke timing (one cold run, not a benchmark): compile+warm {compile_s:.2f} s, "
          f"prefill {prompt.shape[1]} tokens {prefill_s * 1e3:.1f} ms, "
          f"served {stats['tokens_per_s']:.1f} tok/s over {stats['wall_s']:.2f} s, "
          f"decode EMA {loop.tok_rate:.1f} tok/s")
    return stats


def fleet_phase(cfg, run, params, *, n_replicas: int, batch: int, max_len: int, prompt_lens,
                gen: int, seed: int) -> dict:
    """One-chip replicas behind the router, each on its own device."""
    fleet = build_fleet(cfg, run, params, n_replicas, batch, max_len,
                        router="capacity_weighted", mode="arena")
    t = time.perf_counter()
    for rep in fleet.replicas:
        for n in prompt_lens:
            rep.warm(n)
    compile_s = time.perf_counter() - t
    n = n_replicas * batch
    reqs = make_requests(cfg, n, prompt_lens, gen, seed)
    stats = fleet.run_requests(reqs)
    _check_served(reqs, stats, n, gen)
    served = stats["completed_per_replica"]
    check(all(c > 0 for c in served), f"a replica served nothing: {served}")

    homes = []
    for i, rep in enumerate(fleet.replicas):
        devs = {d for x in jax.tree.leaves((rep.params, rep._arena)) for d in x.devices()}
        check(len(devs) == 1, f"replica {i} spans devices {devs}")
        homes.append(devs.pop())
    want_distinct = min(n_replicas, len(jax.devices()))
    check(len(set(homes)) == want_distinct, f"replicas share devices: {homes}")

    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2), (1, 40), 0, cfg.vocab_size)
    probe = probe_fn(cfg, run, 32, max_len)
    logits = [np.asarray(probe(rep.params, np.asarray(tokens)), np.float32)
              for rep in fleet.replicas]
    scale = float(np.abs(logits[0]).max())
    errs = [float(np.abs(x - logits[0]).max()) / scale for x in logits]
    check(all(np.isfinite(x).all() for x in logits), "a replica produced a non-finite logit")
    print(f"fleet: {stats['completed']}/{n} requests over {n_replicas} replicas on "
          f"{[str(d) for d in homes]}, completed per replica {served}, "
          f"redispatched {stats['redispatched']}")
    print(f"check fleet probe: prefill 32 + 8 decode steps per device, max|err|/max|ref| vs "
          f"device 0: {[f'{e:.3e}' for e in errs]} (tol {FLEET_PROBE_RTOL:g})")
    check(max(errs) <= FLEET_PROBE_RTOL, f"replica logits diverge from device 0: {errs}")
    print(f"smoke timing (one cold run, not a benchmark): compile+warm {compile_s:.2f} s for "
          f"{n_replicas} replicas, served {stats['tokens_per_s']:.1f} tok/s fleet-wide over "
          f"{stats['wall_s']:.2f} s")
    return stats


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the fleet phase, one replica per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    check(dev["platform"] == "tpu", f"no TPU: JAX found {dev}")
    check(dev["count"] >= args.chips, f"--chips {args.chips} but JAX found {dev}")
    print(f"device: {dev}")
    print(f"compile cache: {enable_compile_cache()}")

    cfg = get_config(ARCH)
    run = serve_run_config()
    t = time.perf_counter()
    # one jitted init program: op-by-op init compiled each random op apart
    # and took 105 s on a v5e chip
    init = jax.jit(M.init_model, static_argnums=1)
    params = jax.block_until_ready(init(jax.random.PRNGKey(args.seed), cfg))
    print(f"model: {cfg.name} {M.count_params_exact(cfg) / 1e9:.3f}B params "
          f"({cfg.num_layers}L d_model {cfg.d_model} vocab {cfg.vocab_size}), "
          f"decode attention {resolve_decode_impl(run.decode_attention_impl)}, "
          f"init {time.perf_counter() - t:.2f} s")

    if args.chips == 4:
        fleet_phase(cfg, run, params, n_replicas=4, batch=BATCH, max_len=MAX_LEN,
                    prompt_lens=PROMPT_LENS, gen=GEN, seed=args.seed)
    else:
        check_kernel_vs_einsum(cfg, MAX_LEN, BATCH, args.seed)
        check_decode_matches_forward(cfg, run, params, MAX_LEN, args.seed)
        serve_phase(cfg, run, params, batch=BATCH, max_len=MAX_LEN, prompt_lens=PROMPT_LENS,
                    gen=GEN, n_requests=N_REQUESTS, seed=args.seed)
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None:
        print(f"device 0 peak memory in use: {peak / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        sys.exit(1)
