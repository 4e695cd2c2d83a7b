"""FPDT distribution-kind parity on a forced multi-device host platform.

Spawned by tests/test_fpdt_mesh.py (the main pytest process keeps a single
visible device).  Builds a (2 data, 4 model) mesh out of 8 fake CPU devices
and asserts, for the attention pipeline alone (fpdt_attention), that

  * kind="ulysses" (heads % sp == 0) and
  * kind="cp"      (heads % sp != 0 — chunk-streamed KV all-gather)

match the kind="local" single-device oracle on outputs AND grads (x and
every attention param), at u=1 (plain baseline) and u=4 (scan-compiled
chunk pipeline, offload requested).  Exits nonzero on any mismatch; prints
the marker line on success.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.configs import get_config, reduced
from repro.core import fpdt
from repro.core.parallel import ParallelContext
from repro.launch.mesh import make_compat_mesh
from repro.models import layers as L

TOL = dict(rtol=2e-3, atol=2e-3)


def run(cfg, par, p, x, do, kind):
    def f(x, p):
        o = fpdt.fpdt_attention(cfg, par, p, x, kind=kind)
        return (o * do).sum(), o

    g = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
    if par is not None and par.mesh is not None:
        with par.mesh:
            (_, o), grads = jax.jit(g)(x, p)
    else:
        (_, o), grads = jax.jit(g)(x, p)
    return jax.device_get(o), jax.device_get(grads)


def check(kind, heads, kv_heads, u, offload):
    base = reduced(get_config("llama3.2-1b"))
    cfg = dataclasses.replace(
        base, param_dtype="float32", num_heads=heads, num_kv_heads=kv_heads,
        block_q=8, block_k=8, fpdt_chunks=u, fpdt_offload=offload)
    key = jax.random.PRNGKey(0)
    p = L.init_attn(cfg, key, jnp.float32)
    b, S = 2, 64
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, S, cfg.d_model), jnp.float32)
    do = jax.random.normal(jax.random.fold_in(key, 2), (b, S, cfg.q_dim), jnp.float32)

    # single-device oracle: un-chunked local attention
    cfg0 = dataclasses.replace(cfg, fpdt_chunks=1, fpdt_offload=False)
    o0, g0 = run(cfg0, ParallelContext(mesh=None, attn_impl="xla_flash"),
                 p, x, do, "local")

    mesh = make_compat_mesh((2, 4), ("data", "model"))
    par = ParallelContext(mesh=mesh, dp_axes=("data",), attn_impl="xla_flash")
    o1, g1 = run(cfg, par, p, x, do, kind)

    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), **TOL)
    for a, b_ in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), **TOL)
    print(f"OK kind={kind} heads={heads}/{kv_heads} u={u} offload={offload}")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.device_count()
    # ulysses: 4 q heads over sp=4; GQA kv=2 stays replicated over model
    check("ulysses", heads=4, kv_heads=2, u=1, offload=False)
    check("ulysses", heads=4, kv_heads=2, u=4, offload=True)
    # cp: 6 heads don't divide the model axis -> chunk-streamed KV all-gather
    check("cp", heads=6, kv_heads=6, u=1, offload=False)
    check("cp", heads=6, kv_heads=6, u=4, offload=True)
    print("ALL FPDT MESH CHECKS PASSED")
