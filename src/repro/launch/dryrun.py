import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_EXTRA", "")
    + f" --xla_force_host_platform_device_count={os.environ.get('REPRO_DRYRUN_DEVICES', '512')}"
).strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: for each cell we
``jax.jit(step).lower(shapes).compile()`` on placeholder host devices forming
the production mesh, then extract

  * ``compiled.memory_analysis()``  — per-device bytes (fits-in-HBM proof)
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for the roofline
  * collective bytes parsed from the stableHLO/HLO text (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)

Results go to ``results/dryrun/<arch>__<shape>__<mesh>.json``, which
EXPERIMENTS.md §Dry-run/§Roofline and benchmarks/roofline.py consume.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell, single-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, ARCH_IDS, get_config, shape_applicable
from repro.configs.base import RunConfig
from repro.launch.mesh import make_production_mesh, parse_mesh_arg
from repro.launch.steps import cell_artifacts
from repro.roofline.extract import analyze_compiled, probe_cost  # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _compile_cell(cfg, run, shape, mesh):
    art = cell_artifacts(cfg, run, shape, mesh)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            art["fn"],
            in_shardings=art["in_shardings"],
            donate_argnums=art["donate_argnums"],
        )
        lowered = jitted.lower(*art["args"])
        compiled = lowered.compile()
    return lowered, compiled


def _probe_run(run: RunConfig, shape) -> RunConfig:
    """Probe compiles unroll every inner scan so HloCostAnalysis counts all
    iterations; bigger chunks bound the unrolled body count. Probes are never
    executed, so their HBM footprint is irrelevant."""
    # NOTE: ssd_chunk is NOT raised here — unlike the attention chunk (a pure
    # tiling choice), the SSD chunk length L changes the algorithm's real FLOPs
    # (the L×L intra-chunk term), so probes must keep the production value.
    return dataclasses.replace(
        run,
        scan_unroll=True,
        attention_chunk=min(8192, max(run.attention_chunk, shape.seq_len // 4 or 1)),
    )


def run_cell(
    arch: str,
    shape_name: str,
    mesh,
    run: RunConfig,
    tag: str,
    out_dir: Path,
    probes: bool = True,
    cfg_overrides: dict | None = None,
):
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": tag,
        "mesh_shape": list(mesh.devices.shape),
        "run": {
            "fsdp": run.fsdp,
            "sequence_parallel": run.sequence_parallel,
            "remat": run.remat,
            "attention_impl": run.attention_impl,
            "attention_chunk": run.attention_chunk,
            "grad_accum_steps": run.grad_accum_steps,
            "pad_attention_heads_to": run.pad_attention_heads_to,
            "optimizer_dtype": run.optimizer_dtype,
        },
    }
    t0 = time.time()
    try:
        # 1) production artifact: full depth, rolled scans → compile + memory proof
        lowered, compiled = _compile_cell(cfg, run, shape, mesh)
        t_compile = time.time() - t0
        # 2) cost probes: 1-period and 2-period depth, inner scans unrolled →
        #    per-period deltas extrapolate to full depth (scan bodies are
        #    otherwise counted once by HloCostAnalysis; see roofline/extract)
        probe_costs = None
        if probes:
            pr = _probe_run(run, shape)
            probe_costs = []
            for k in (1, 2):
                cfg_k = dataclasses.replace(cfg, num_layers=k * cfg.period)
                _, comp_k = _compile_cell(cfg_k, pr, shape, mesh)
                probe_costs.append(probe_cost(comp_k, mesh))
        analysis = analyze_compiled(
            cfg, shape, mesh, lowered, compiled, probe_costs=probe_costs
        )
        rec.update(analysis)
        rec["ok"] = True
        rec["compile_s"] = round(t_compile, 2)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)

    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / f"{arch}__{shape_name}__{tag}.json"
    out.write_text(json.dumps(rec, indent=2, default=str))
    status = "OK " if rec.get("ok") else "FAIL"
    print(f"[{status}] {arch:24s} {shape_name:12s} {tag:10s} {rec['total_s']:8.1f}s", flush=True)
    if not rec.get("ok"):
        print("      " + rec["error"], flush=True)
    return rec


def build_run(args, arch: str) -> RunConfig:
    return RunConfig(
        fsdp=not args.no_fsdp,
        sequence_parallel=not args.no_sp,
        remat=args.remat,
        attention_impl=args.attention_impl,
        attention_chunk=args.attention_chunk,
        grad_accum_steps=args.grad_accum,
        pad_attention_heads_to=args.pad_heads,
        optimizer_dtype=args.opt_dtype,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None, help="arch id (repeatable)")
    ap.add_argument("--cell", action="append", default=None, help="explicit arch:shape cell (repeatable)")
    ap.add_argument("--shape", action="append", default=None, choices=list(SHAPES), help="shape (repeatable)")
    ap.add_argument("--all", action="store_true", help="all applicable cells")
    ap.add_argument("--multi-pod", action="store_true", help="2×16×16 mesh instead of 16×16")
    ap.add_argument("--mesh", default=None, help="override mesh, e.g. 2x4 / 2x2x4")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--attention-impl", default="chunked", choices=["xla", "chunked"])
    ap.add_argument("--attention-chunk", type=int, default=1024)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-sp", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--pad-heads", type=int, default=0)
    ap.add_argument("--opt-dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--moe-group", type=int, default=0, help="override cfg.moe_group_size")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    ap.add_argument("--tag", default=None, help="override result-file mesh tag")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-probes", action="store_true", help="production compile only (multi-pod pass)")
    args = ap.parse_args()

    if args.mesh:
        mesh = parse_mesh_arg(args.mesh)
        tag = args.tag or args.mesh
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        tag = args.tag or ("multipod" if args.multi_pod else "singlepod")

    archs = args.arch or list(ARCH_IDS)
    shapes = args.shape or list(SHAPES)
    out_dir = Path(args.out)

    if args.cell:
        cells = [tuple(c.split(":", 1)) for c in args.cell]
    else:
        cells = []
        for arch in archs:
            cfg = get_config(arch)
            for sh in shapes:
                if not shape_applicable(cfg, SHAPES[sh]):
                    print(f"[SKIP] {arch:24s} {sh:12s} (full attention: long-context n/a, DESIGN.md §5)")
                    continue
                cells.append((arch, sh))

    n_ok = 0
    for arch, sh in cells:
        if args.skip_existing and (out_dir / f"{arch}__{sh}__{tag}.json").exists():
            prev = json.loads((out_dir / f"{arch}__{sh}__{tag}.json").read_text())
            if prev.get("ok"):
                n_ok += 1
                print(f"[SKIP-OK] {arch:24s} {sh:12s} (cached)")
                continue
        over = {"moe_group_size": args.moe_group} if args.moe_group else None
        rec = run_cell(arch, sh, mesh, build_run(args, arch), tag, out_dir,
                       probes=not args.no_probes, cfg_overrides=over)
        n_ok += bool(rec.get("ok"))
    print(f"\n{n_ok}/{len(cells)} cells compiled OK on mesh {tag} {mesh.devices.shape}")
    if n_ok < len(cells):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
