import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init).  For every cell this driver:
  1. builds the production mesh ((16,16) single-pod / (2,16,16) multi-pod),
  2. jit-lowers the cell's step function over ShapeDtypeStruct inputs with
     the production in/out shardings,
  3. .compile()s it (sharding mismatches, OOM-at-compile, unsupported
     collectives all fail HERE),
  4. records memory_analysis / cost_analysis / per-collective byte counts
     (parsed from the compiled HLO) into experiments/dryrun/*.json for the
     roofline analysis (EXPERIMENTS.md reads these).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, shape_applicable
from repro.core.parallel import ParallelContext
from repro.launch import steps as ST
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.hlo import count_ops
from repro.launch.mesh import dp_axes_of, make_production_mesh
from repro.runtime.placement import PlacementPolicy

COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=?\s*.*?"
)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2,
}


def _op_output_bytes(line: str) -> int:
    """Bytes of the op's output (shape text between '=' and the op name)."""
    try:
        rhs = line.split("=", 1)[1]
    except IndexError:
        return 0
    rhs = rhs.lstrip()
    if rhs.startswith("("):  # tuple-shaped output: take up to the matching ')'
        head = rhs[: rhs.index(")") + 1]
    else:  # cut at the op call's '(' so operand shapes aren't counted
        head = rhs.split("(", 1)[0]
    total = 0
    for dt, dims in SHAPE_RE.findall(head):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str):
    """Sum output bytes per collective kind (done-ops skipped: counted at start)."""
    out = {}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(
            r".*= \S+ (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
            r"(?:-start)?\(", ls)
        if not m:
            # tuple-shaped lhs: "%x = (f32[..],..) all-gather-start(..."
            m = re.match(
                r".*\) (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
                r"(?:-start)?\(", ls)
        if not m:
            continue
        if "-done" in ls.split("=")[1][:40]:
            continue
        kind = m.group(1)
        b = _op_output_bytes(ls)
        d = out.setdefault(kind, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, chunks=None, offload=None,
             outdir: str = "experiments/dryrun") -> dict:
    from repro.configs import SHAPES

    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    # NOTE: offload disabled for the big-mesh dry-run: XLA:CPU's SPMD
    # partitioner rejects annotate_device_placement custom-calls produced by
    # in-graph host offload at this scale ("side-effect ops cannot be
    # replicated") — a backend limitation, not a sharding bug; the offload
    # path compiles+runs at the 8-device mesh (tests) and in the host-KV
    # decode cells.  Chunking semantics are unchanged ("FPDT w. chunking").
    # The disable is expressed through the placement policy (the single
    # layer that owns memory-kind decisions), not ad-hoc flags downstream.
    pol = PlacementPolicy.probe(mesh.devices.flat[0], offload_enabled=False)
    par = ParallelContext(mesh=mesh, dp_axes=dp_axes_of(mesh), attn_impl="xla_flash",
                          offload_to_host=False, placement=pol)
    cfg = ST.tuned_config(get_config(arch), shape, chunks=chunks, offload=offload)
    n_host_chunks = 0
    if shape.kind == "decode" and shape.seq_len >= 500_000 and cfg.family in ("dense",):
        # EXTRA cell: FPDT host-streamed KV decode.  --chunks sweeps the
        # host-KV chunk count here (the decode-side analogue of u; the
        # scan-compiled decode keeps program size flat in it).
        n_host_chunks = chunks if chunks else 8
        if shape.seq_len % n_host_chunks:
            # _decode_attention silently falls back to on-device attention
            # for non-dividing chunk counts — that would record numbers for
            # the wrong program under this cell's label
            raise ValueError(
                f"--chunks {n_host_chunks} does not divide the decode cache "
                f"length {shape.seq_len}; the host-streamed path requires "
                f"equal slabs")
    rec = {
        "arch": arch, "shape": shape_name, "mesh": "multi" if multi_pod else "single",
        "kind": shape.kind, "chunks": cfg.fpdt_chunks, "offload": cfg.fpdt_offload,
        "n_host_chunks": n_host_chunks,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "params": cfg.num_params(), "active_params": cfg.num_active_params(),
    }
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, donate = ST.build(cfg, par, shape, n_host_chunks=n_host_chunks)
        with mesh:
            jf = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
            lowered = jf.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "host_temp_bytes": ma.host_temp_size_in_bytes,
            "host_argument_bytes": ma.host_argument_size_in_bytes,
        }
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        hlo_text = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo_text)
        # program size: the scan-compiled FPDT/layer loops must keep this
        # ~flat in fpdt_chunks and depth (see benchmarks/compile_scaling.py)
        rec["hlo_ops"] = count_ops(hlo_text)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    os.makedirs(outdir, exist_ok=True)
    suffix = "" if chunks is None else f"_u{chunks}" + ("off" if offload else "")
    fn_out = os.path.join(outdir, f"{arch}_{shape_name}_{rec['mesh']}{suffix}.json")
    with open(fn_out, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK " if rec["ok"] else "FAIL"
    print(f"[{status}] {arch:28s} {shape_name:12s} {rec['mesh']:6s} "
          f"lower={rec.get('lower_s','-')}s compile={rec.get('compile_s','-')}s "
          f"temp={rec.get('memory',{}).get('temp_bytes',0)/2**30:.2f}GiB"
          + ("" if rec["ok"] else f"  {rec['error'][:150]}"))
    return rec


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--chunks", type=int, default=None)
    ap.add_argument("--offload", action="store_true", default=None)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                if shape_applicable(a, s):
                    cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    fails = 0
    for a, s in cells:
        for mp in meshes:
            rec = run_cell(a, s, mp, chunks=args.chunks, offload=args.offload, outdir=args.out)
            fails += 0 if rec["ok"] else 1
    print(f"\n{len(cells) * len(meshes) - fails} ok, {fails} failed")
    raise SystemExit(1 if fails else 0)


if __name__ == "__main__":
    main()
