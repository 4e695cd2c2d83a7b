import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Roofline analysis per (arch x shape x mesh) — EXPERIMENTS.md §Roofline.

Terms (assignment formulas, TPU v5e constants):
    compute    = FLOPs / (chips * 197e12)
    memory     = HBM bytes / (chips * 819e9)
    collective = collective bytes per chip / 50e9

Sources:
  * compute/memory: the analytic model (benchmarks/flops_model.py) — exact
    closed form; XLA cost_analysis counts lax.scan bodies once, so raw
    compiled numbers under-report (the HLO-probe cross-check column shows
    this measured and corrected).
  * collective: PROBE-measured from the real compiled artifact — two
    scan-unrolled compiles (1 cycle and 2 cycles of the layer pattern)
    isolate the true per-cycle collective bytes (probe2 - probe1); total =
    outside + n_cycles * per_cycle.  This is the number §Perf hillclimbs.
  * capacity: per-device memory_analysis from the full-depth compile
    (experiments/dryrun/*.json).

Usage:
  python -m benchmarks.roofline --arch llama3.2-1b --shape train_4k [--multi]
  python -m benchmarks.roofline --all      # every runnable cell, single-pod
"""
import argparse
import dataclasses
import json
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, shape_applicable
from repro.core.parallel import ParallelContext
from repro.launch import steps as ST
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import dp_axes_of, make_production_mesh

from benchmarks import flops_model as FM


def _probe_cfg(cfg, n_cycles: int):
    """Scan-unrolled shallow config whose HLO costs scale with true depth."""
    from repro.models.transformer import pattern_of

    pat = pattern_of(cfg)
    return dataclasses.replace(
        cfg,
        num_layers=n_cycles * len(pat),
        scan_layers=False,
        loss_chunks=1,       # no loss scan -> loss counted exactly
        mlp_chunks=1,        # no FFN-chunk scan in probes
        # cost analysis counts a chunk loop's body once; FPDT's attention
        # math and all-to-all bytes are u = 1's, whose program has no loop
        fpdt_chunks=1, fpdt_offload=False,
    )


def _probe_costs(cfg, par, shape, mesh, n_cycles: int, n_host_chunks=0):
    from repro.launch.dryrun import parse_collectives

    pc = _probe_cfg(cfg, n_cycles)
    fn, args, in_sh, out_sh, donate = ST.build(pc, par, shape, n_host_chunks=n_host_chunks)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": sum(v["bytes"] for v in colls.values()),
        "colls": colls,
    }


def probe_collectives(arch: str, shape_name: str, multi_pod: bool,
                      chunks=None, offload=None):
    """(per-chip collective bytes, detail) for the full-depth model."""
    from repro.models.transformer import layout_of

    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    par = ParallelContext(mesh=mesh, dp_axes=dp_axes_of(mesh), attn_impl="xla_flash",
                          offload_to_host=False)
    cfg = ST.tuned_config(get_config(arch), shape, chunks=chunks, offload=offload)
    n_host = 8 if (shape.kind == "decode" and shape.seq_len >= 500_000
                   and cfg.family == "dense") else 0
    pat, n_cycles, tail = layout_of(cfg)
    p1 = _probe_costs(cfg, par, shape, mesh, 1, n_host)
    p2 = _probe_costs(cfg, par, shape, mesh, 2, n_host)
    per_cycle = {k: p2[k] - p1[k] for k in ("flops", "bytes", "coll_bytes")}
    outside = {k: p1[k] - per_cycle[k] for k in per_cycle}
    kinds = set(p1["colls"]) | set(p2["colls"])
    per_cycle_kinds = {
        k: {"bytes": p2["colls"].get(k, {}).get("bytes", 0) - p1["colls"].get(k, {}).get("bytes", 0),
            "count": p2["colls"].get(k, {}).get("count", 0) - p1["colls"].get(k, {}).get("count", 0)}
        for k in kinds
    }
    frac_tail = len(tail) / len(pat) if tail else 0.0
    total = {k: max(0.0, outside[k]) + per_cycle[k] * (n_cycles + frac_tail)
             for k in per_cycle}
    return total, {"per_cycle": per_cycle, "outside": outside,
                   "per_cycle_kinds": per_cycle_kinds,
                   "outside_kinds": p1["colls"],
                   "n_cycles": n_cycles, "probe1": p1, "probe2": p2}


def analyze_cell(arch: str, shape_name: str, multi_pod: bool = False,
                 chunks=None, offload=None, outdir="experiments/roofline"):
    shape = SHAPES[shape_name]
    chips = 512 if multi_pod else 256
    cfg = ST.tuned_config(get_config(arch), shape, chunks=chunks, offload=offload)
    probed, detail = probe_collectives(arch, shape_name, multi_pod, chunks, offload)
    terms = FM.terms(cfg, shape, chips, collective_bytes_per_chip=probed["coll_bytes"])
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single", "chips": chips,
        "chunks": cfg.fpdt_chunks, "offload": cfg.fpdt_offload,
        **{k: terms[k] for k in ("t_compute", "t_memory", "t_collective",
                                 "bottleneck", "roofline_frac", "useful_ratio")},
        "analytic_flops": terms["flops_total"],
        "hlo_flops_extrapolated": probed["flops"],
        "analytic_hbm_bytes": terms["hbm_bytes"],
        "hlo_bytes_extrapolated": probed["bytes"],
        "coll_bytes_per_chip": probed["coll_bytes"],
        "model_flops": terms["model_flops"],
        "probe_detail": {k: detail[k] for k in ("per_cycle", "outside", "per_cycle_kinds", "outside_kinds", "n_cycles")},
    }
    os.makedirs(outdir, exist_ok=True)
    suffix = "" if chunks is None else f"_u{chunks}" + ("off" if offload else "")
    with open(os.path.join(outdir, f"{arch}_{shape_name}_{rec['mesh']}{suffix}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"{arch:28s} {shape_name:12s} {rec['mesh']:6s} "
          f"C={terms['t_compute']*1e3:9.2f}ms M={terms['t_memory']*1e3:9.2f}ms "
          f"X={terms['t_collective']*1e3:9.2f}ms -> {terms['bottleneck']:10s} "
          f"frac={terms['roofline_frac']:.2f} useful={terms['useful_ratio']:.2f}")
    return rec


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--chunks", type=int, default=None)
    ap.add_argument("--offload", action="store_true", default=None)
    ap.add_argument("--out", default="experiments/roofline")
    args = ap.parse_args()
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                if shape_applicable(a, s):
                    try:
                        analyze_cell(a, s, args.multi, outdir=args.out)
                    except Exception as e:  # noqa: BLE001
                        print(f"{a:28s} {s:12s} FAILED {type(e).__name__}: {str(e)[:160]}")
    else:
        analyze_cell(args.arch, args.shape, args.multi, chunks=args.chunks,
                     offload=args.offload, outdir=args.out)


if __name__ == "__main__":
    main()
