"""Benchmark runner: one function per paper table/figure + kernel micro-bench.

  PYTHONPATH=src python -m benchmarks.run           # all, CSV to stdout
  PYTHONPATH=src python -m benchmarks.run --only table1 fig11
  PYTHONPATH=src python -m benchmarks.run --only serve --json BENCH_serve.json

``--json`` additionally writes the selected suites' rows as machine-
readable JSON (``{suite: [{name, value, derived}]}``), the format the
``BENCH_*.json`` perf-trajectory files use so future PRs can
regression-track numbers like serving tokens/s and p50/p95 inter-token
latency without parsing stdout.

Roofline sweeps (compile-heavy) run separately:
  python -m repro.launch.dryrun --all     -> experiments/dryrun/
  python -m benchmarks.roofline --all     -> experiments/roofline/
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _row_to_record(row: str):
    """'bench,name,value,derived' -> {name, value, derived} (floats parsed);
    header rows return None."""
    parts = row.split(",")
    if len(parts) < 3 or parts[1] in ("name", "ERROR"):
        return None
    name, value = parts[1], parts[2]
    try:
        value = float(value)
    except ValueError:
        pass
    return {"name": name, "value": value,
            "derived": parts[3] if len(parts) > 3 else ""}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write suite results as JSON (BENCH_*.json "
                         "perf-trajectory format)")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    from benchmarks import compile_scaling
    from benchmarks import kernels_bench
    from benchmarks import paper_tables as PT
    from benchmarks import serve_bench

    suites = {
        "table1": PT.table1_max_context,
        "fig10": PT.fig10_latency,
        "fig11": PT.fig11_mfu,
        "fig12": PT.fig12_chunk_sweep,
        "table3": PT.table3_strategies,
        "table4": PT.table4_sparse,
        "kernels": kernels_bench.run,
        "compile_scaling": compile_scaling.run,
        "serve": serve_bench.run,
        "paged": serve_bench.run_paged,
        "serve_mesh": serve_bench.run_serve_mesh,
        "kv_store": serve_bench.run_kv_store,
        "slo": serve_bench.run_slo,
        "failover": serve_bench.run_failover,
        "obs": serve_bench.run_obs,
    }
    sel = args.only or list(suites)
    failures = 0
    results = {}
    for name in sel:
        records = results[name] = []  # always a list of {name, value, derived}
        try:
            for row in suites[name]():
                print(row)  # incremental — rows survive a later crash
                rec = _row_to_record(row)
                if rec:
                    records.append(rec)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            records.append({"name": "ERROR", "value": f"{type(e).__name__}: {e}",
                            "derived": ""})
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(results, fh, indent=1)
            fh.write("\n")
        print(f"wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
