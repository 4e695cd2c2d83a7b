"""PlacementPolicy: capability probing, no-op degradation on CPU only (an
accelerator without a host pool refuses offload), spec pass-through with a
mesh, and the FPDT offload regression against the u=1 baseline."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import fpdt
from repro.core.parallel import ParallelContext
from repro.models import layers as L
from repro.runtime.placement import PlacementPolicy, default_policy


# ---------------------------------------------------------------------------
# capability probing
# ---------------------------------------------------------------------------


def test_probe_cpu_backend():
    pol = PlacementPolicy.probe(jax.devices()[0])
    assert pol.backend == jax.devices()[0].platform
    assert pol.device_kind == jax.devices()[0].default_memory().kind
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    # jax 0.9's CPU backend advertises a pinned_host pool; older ones did not
    if "pinned_host" not in kinds:
        assert not pol.supports_pinned_host
        assert not pol.can_offload


def test_default_policy_probes_once():
    assert default_policy() is default_policy()


def test_host_pool_equal_to_default_is_not_offload():
    # a "host" pool that IS the default memory is not an offload target
    pol = PlacementPolicy(device_kind="pinned_host", host_kind=None)
    assert not pol.can_offload
    pol2 = PlacementPolicy(device_kind="device", host_kind="pinned_host")
    assert pol2.supports_pinned_host and pol2.can_offload
    assert not dataclasses.replace(pol2, offload_enabled=False).can_offload


# ---------------------------------------------------------------------------
# no-op degradation
# ---------------------------------------------------------------------------


def test_noop_degradation_without_host_pool():
    pol = PlacementPolicy(device_kind="unpinned_host", host_kind=None,
                          backend="cpu")
    x = jnp.arange(8.0)
    assert pol.to_host(x) is x
    assert pol.to_device(x) is x


def test_noop_logs_warning_once(caplog, monkeypatch):
    from repro.runtime import placement

    monkeypatch.setattr(placement, "_WARNED", set())
    pol = PlacementPolicy(device_kind="unpinned_host", host_kind=None,
                          backend="cpu")
    x = jnp.arange(4.0)
    with caplog.at_level("WARNING", logger="repro.runtime.placement"):
        pol.to_host(x)
        pol.to_host(x)
    hits = [r for r in caplog.records if "backend 'cpu'" in r.message]
    assert len(hits) == 1  # warn once, not per chunk


def test_remat_policy_degrades_to_full_remat():
    pol = PlacementPolicy(device_kind="unpinned_host", host_kind=None,
                          backend="cpu")
    assert pol.remat_policy() is jax.checkpoint_policies.nothing_saveable


@pytest.mark.parametrize("verb", ["to_host", "to_device", "remat_policy"])
def test_accelerator_without_host_pool_refuses_offload(verb):
    """Requested offload on a TPU with no pinned_host pool is an error, not
    a silent no-op; an operator who disabled offload still gets identity."""
    pol = PlacementPolicy(device_kind="device", host_kind=None, backend="tpu")
    x = jnp.arange(4.0)
    args = () if verb == "remat_policy" else (x,)
    with pytest.raises(RuntimeError, match="tpu"):
        getattr(pol, verb)(*args)
    off = dataclasses.replace(pol, offload_enabled=False)
    if verb != "remat_policy":
        assert getattr(off, verb)(x) is x


# ---------------------------------------------------------------------------
# spec pass-through with a mesh
# ---------------------------------------------------------------------------


def test_sharding_spec_passthrough_with_mesh():
    from repro.launch.mesh import make_compat_mesh

    mesh = make_compat_mesh((1,), ("data",))
    pol = default_policy()
    s = pol.host_sharding(mesh, "data", None)
    assert s is not None and s.mesh is mesh
    assert s.spec == jax.sharding.PartitionSpec("data", None)
    if not pol.can_offload:  # degraded: plain default-memory sharding
        x = jnp.ones((2, 3))
        y = jax.device_put(x, s)  # must be constructible and usable
        np.testing.assert_allclose(np.asarray(y), np.asarray(x))
    assert pol.ns(None) is None  # mesh-less spec degrades to None


def test_parallel_context_routes_through_policy():
    par = ParallelContext(mesh=None)
    x = jnp.arange(6.0).reshape(2, 3)
    hx = par.to_host(x)
    dx = par.to_device(hx)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(x))
    if not par.pol.can_offload:
        assert not par.offload_active
    # offload disabled at the context level short-circuits entirely
    par_off = ParallelContext(mesh=None, offload_to_host=False)
    assert par_off.to_host(x) is x


# ---------------------------------------------------------------------------
# regression: FPDT offload on a host without pinned memory == u=1 baseline
# ---------------------------------------------------------------------------


def test_fpdt_offload_matches_baseline_without_pinned_memory():
    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")),
                              param_dtype="float32", block_q=16, block_k=16)
    key = jax.random.PRNGKey(0)
    p = L.init_attn(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, cfg.d_model),
                          jnp.float32)

    def run(u, offload):
        c = dataclasses.replace(cfg, fpdt_chunks=u, fpdt_offload=offload)
        par = ParallelContext(mesh=None, attn_impl="pallas")
        return fpdt.fpdt_attention(c, par, p, x, kind="local")

    o1 = run(1, False)
    o4 = run(4, True)  # seed: ValueError before any math on this backend
    np.testing.assert_allclose(np.asarray(o4), np.asarray(o1),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fori_double_buffered without ``live``: every index fetched once, no tail
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lo,hi,traced", [(0, 5, False), (0, 5, True), (3, 4, True),
                                          (2, 2, False), (4, 2, False), (2, 2, True),
                                          (4, 2, True)])
def test_dense_double_buffering_fetches_each_index_once(lo, hi, traced):
    """Without ``live`` ``fetch`` runs exactly hi - lo times, for lo..hi-1 in
    order, with no ``lax.cond`` inside the loop (no clamped tail fetch);
    each body sees its own index's buffer.  An empty range fetches nothing
    and returns ``init``: a traced range through the one ``lax.cond`` around
    the loop, a range of Python ints without it."""
    from repro.runtime.placement import fori_double_buffered

    fetched = []

    def fetch(idx):
        jax.debug.callback(lambda i: fetched.append(int(i)), idx, ordered=True)
        return jnp.stack([idx, 10 * idx])

    def body(idx, buf, carry):
        total, wrong = carry
        return total + buf[1], wrong + (buf[0] != idx)

    def run(lo, hi):
        return fori_double_buffered(lo, hi, fetch, body, (jnp.int32(0), jnp.int32(0)))

    if traced:
        jaxpr = jax.make_jaxpr(run)(lo, hi).jaxpr
        (guard,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
        (loop,) = [e for b in guard.params["branches"] for e in b.jaxpr.eqns
                   if e.primitive.name == "while"]
        assert "cond[" not in str(loop.params["body_jaxpr"])
        total, wrong = jax.jit(run)(lo, hi)
    else:
        total, wrong = run(lo, hi)
    jax.effects_barrier()
    assert fetched == list(range(lo, hi))
    assert int(total) == 10 * sum(range(lo, hi)) and int(wrong) == 0
