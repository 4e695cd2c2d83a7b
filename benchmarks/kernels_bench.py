"""Kernel micro-benchmarks (CPU wall-clock; interpret-mode Pallas is a
correctness vehicle here — TPU timing comes from the roofline model).

Emits name,us_per_call,derived rows for benchmarks.run.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def _time(f, *args, iters=3) -> float:
    f(*args)  # compile
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> List[str]:
    from repro.kernels.flash_attention import ops as FA
    from repro.kernels.linear_scan import ops as LS

    rng = np.random.default_rng(0)
    rows = ["bench,name,us_per_call,derived"]
    b, h, s, d = 1, 4, 256, 64
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    for impl in ("xla_flash", "ref"):
        f = jax.jit(lambda q, k, v, impl=impl: FA.flash_attention(q, k, v, impl=impl,
                                                                  block_q=64, block_k=64))
        us = _time(f, q, k, v)
        flops = 4 * b * h * s * s * d / 2
        rows.append(f"bench,flash_attn_{impl}_{s},{us:.0f},{flops / (us * 1e-6) / 1e9:.1f}GFLOPs")
    a = jnp.asarray(rng.uniform(0.5, 0.99, (2, 512, 64)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 512, 64)), jnp.float32)
    for impl in ("xla",):
        f = jax.jit(lambda a, x, impl=impl: LS.linear_scan(a, x, impl=impl))
        us = _time(f, a, x)
        rows.append(f"bench,linear_scan_{impl}_512,{us:.0f},{2*512*64*2/(us*1e-6)/1e6:.1f}Melem/s")

    # FPDT chunk pipeline fwd+bwd (scan-compiled loops)
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.core import fpdt as FP
    from repro.core.parallel import ParallelContext
    from repro.models import layers as L

    cfg0 = dataclasses.replace(reduced(get_config("llama3.2-1b")),
                               param_dtype="float32", block_q=16, block_k=16)
    p = L.init_attn(cfg0, jax.random.PRNGKey(0), jnp.float32)
    u, S = 8, 128
    xh = jnp.asarray(rng.standard_normal((1, S, cfg0.d_model)), jnp.float32)
    par = ParallelContext(mesh=None, attn_impl="xla_flash")
    cfgu = dataclasses.replace(cfg0, fpdt_chunks=u, fpdt_offload=True)
    f = jax.jit(jax.grad(lambda x: FP.fpdt_attention(cfgu, par, p, x, kind="local").sum()))
    us = _time(f, xh)
    rows.append(f"bench,fpdt_grad_u{u}_scan,{us:.0f},S{S}")
    return rows
