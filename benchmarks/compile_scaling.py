"""Compile-time / program-size scaling of the FPDT chunk pipeline in u.

The paper's 2M-token setting needs large chunk counts (u=32/u=64 at 64K
tokens per chunk).  A Python-unrolled Fig. 7 backward emits O(u^2)
chunk-pair kernels, so jaxpr/HLO size — and with it trace, lower, and
compile time — grows quadratically, capping practical u at toy scale.
The scan-compiled pipeline traces the chunk body once; this benchmark
measures it over a u sweep at fixed chunk length (so sequence length grows
with u, as in the paper's scaling runs) and reports:

  * traced jaxpr equation count (recursive, incl. scan/cond/while bodies)
  * StableHLO op count of the lowered module
  * trace+lower wall-clock

Emits name,value rows for benchmarks.run plus a JSON blob; the slow tier-1
regression test (tests/test_compile_scaling.py) asserts the scan path's
near-O(1) growth so an unrolled schedule never silently comes back.

Usage: python benchmarks/compile_scaling.py [--json out.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import List

import jax
import jax.numpy as jnp

CQ = 8  # tokens per chunk: S = u * CQ grows with u, like the paper's sweep


def _subjaxprs(params):
    for v in params.values():
        stack = [v]
        while stack:
            x = stack.pop()
            if isinstance(x, (tuple, list)):
                stack.extend(x)
            elif type(x).__name__ == "ClosedJaxpr":
                yield x.jaxpr
            elif type(x).__name__ == "Jaxpr":
                yield x


def count_eqns(jaxpr) -> int:
    """Total equation count of a (Closed)Jaxpr including nested bodies —
    the trace-level proxy for program size (scan bodies count once)."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in _subjaxprs(eqn.params):
            n += count_eqns(sub)
    return n


def count_hlo_ops(lowered) -> int:
    """Assignment count in the lowered StableHLO text (loop bodies once) —
    the same heuristic the dry-run records as ``hlo_ops``."""
    from repro.launch.hlo import count_ops

    return count_ops(lowered.as_text())


def build(u: int):
    from repro.configs import get_config, reduced
    from repro.core import fpdt
    from repro.core.parallel import ParallelContext
    from repro.models import layers as L

    cfg = dataclasses.replace(
        reduced(get_config("llama3.2-1b")), param_dtype="float32",
        fpdt_chunks=u, fpdt_offload=True,
        block_q=CQ, block_k=CQ)
    par = ParallelContext(mesh=None, attn_impl="xla_flash")
    S = u * CQ
    key = jax.random.PRNGKey(0)
    p = L.init_attn(cfg, key, jnp.float32)
    x = jnp.zeros((1, S, cfg.d_model), jnp.float32)
    do = jnp.zeros((1, S, cfg.q_dim), jnp.float32)

    def f(x, p):
        o = fpdt.fpdt_attention(cfg, par, p, x, kind="local")
        return (o * do).sum()

    return jax.value_and_grad(f, argnums=(0, 1)), (x, p)


def measure(u: int) -> dict:
    f, args = build(u)
    t0 = time.perf_counter()
    jaxpr = jax.make_jaxpr(f)(*args)
    trace_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    lowered = jax.jit(f).lower(*args)
    lower_s = time.perf_counter() - t0
    return {
        "u": u, "seq_len": u * CQ,
        "jaxpr_eqns": count_eqns(jaxpr),
        "hlo_ops": count_hlo_ops(lowered),
        "trace_s": round(trace_s, 3), "lower_s": round(lower_s, 3),
    }


def sweep(us=(2, 4, 8, 16, 32, 64)) -> List[dict]:
    recs = []
    for u in us:
        recs.append(measure(u))
        print("u={u:<3d} S={seq_len:<5d} jaxpr_eqns={jaxpr_eqns:<6d} "
              "hlo_ops={hlo_ops:<6d} trace={trace_s}s lower={lower_s}s"
              .format(**recs[-1]))
    return recs


def run() -> List[str]:
    """benchmarks.run entry: summarized growth factors."""
    by = {r["u"]: r for r in sweep(us=(4, 32))}
    g = by[32]["hlo_ops"] / by[4]["hlo_ops"]
    return ["bench,name,value,derived", f"bench,fpdt_scan_hlo_growth_u4_to_u32,{g:.3f},x"]


def main():
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    recs = sweep()
    scan = {r["u"]: r for r in recs}
    print(f"\nscan-path growth u=4 -> u=32: "
          f"jaxpr x{scan[32]['jaxpr_eqns'] / scan[4]['jaxpr_eqns']:.2f}, "
          f"hlo x{scan[32]['hlo_ops'] / scan[4]['hlo_ops']:.2f}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(recs, fh, indent=1)


if __name__ == "__main__":
    sys.path.insert(0, "src")
    main()
