#!/usr/bin/env python3
"""Chip benchmark of the FPDT training path: one run of one cell.

  python3 perfbench/run.py --workload gpt-6.7b.fpdt-64k --seed 7 --seconds 10 --trace 0

Runs on the machine it is started on and needs a TPU with as many chips as
the cell asks for; without them it exits non-zero and prints no result.
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; last comes ``checked``, each compared number with its limit.
"""
import os
import time


def _process_start() -> float:
    """``time.perf_counter()`` at this process's start (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.perf_counter() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


T0 = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import harness

    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace), T0)
    except (harness.CellError, OSError, KeyError) as e:
        print(f"[perfbench] no result: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
