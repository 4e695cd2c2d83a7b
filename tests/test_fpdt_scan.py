"""Differential test: the scan-compiled FPDT pipeline and the u = 1 plain
baseline must reproduce an independent dense reference — materialised fp32
attention under the exact causal, window and ``pair_live`` token mask —
outputs and every grad, for u in {1, 2, 4, 8}, offload on/off, both kernel
impls, and for the grouped forward (two query chunks per fetched KV chunk at
even u, one at odd u) under window and sparsity schedules.

The reference shares no code with the pipeline beyond the projection and
rope of ``models.layers``, and runs under ``default_matmul_precision
("highest")`` with it; the tolerance is set an order of magnitude tighter
than the fp32 pipeline's baseline tolerance (5e-4) to catch any
*algorithmic* divergence while allowing last-ulp noise.  Also covers the
sparse schedule: grads of chunk pairs skipped by pair_live must match the
dense-mask reference (zero off-schedule dk/dv contributions, finite dq
everywhere).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import fpdt
from repro.core.parallel import ParallelContext
from repro.models import layers as L

TIGHT = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), param_dtype="float32")
    key = jax.random.PRNGKey(7)
    p = L.init_attn(cfg, key, jnp.float32)
    b, S = 2, 32
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, S, cfg.d_model), jnp.float32)
    do = jax.random.normal(jax.random.fold_in(key, 2), (b, S, cfg.q_dim), jnp.float32)
    return cfg, p, x, do


def _run(cfg, p, x, do, u, offload, impl, *, window=0, sparsity=0.0):
    c = dataclasses.replace(cfg, fpdt_chunks=u, fpdt_offload=offload, block_q=8,
                            block_k=8, attn_sparsity=sparsity)
    par = ParallelContext(mesh=None, attn_impl=impl)

    def f(x, p):
        o = fpdt.fpdt_attention(c, par, p, x, kind="local", window=window)
        return (o * do).sum(), o

    (_, o), grads = jax.jit(
        jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(x, p)
    return o, grads


def _assert_trees_match(g, gu, **tol):
    la, lb = jax.tree.leaves(g), jax.tree.leaves(gu)
    assert len(la) == len(lb)
    for a, b_ in zip(la, lb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), **tol)


def _assert_matches_dense(cfg, p, x, do, u, offload, impl, *, window=0, sparsity=0.0):
    with jax.default_matmul_precision("highest"):
        o, g = _run(cfg, p, x, do, u, offload, impl, window=window, sparsity=sparsity)
        o_ref, g_ref = _dense_sparse_reference(cfg, p, x, do, u, window, sparsity)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), **TIGHT)
    _assert_trees_match(g, g_ref, **TIGHT)


@pytest.mark.parametrize("u,offload,impl", [
    (2, False, "pallas"), (2, True, "xla_flash"),
    (4, True, "pallas"), (4, False, "xla_flash"),
    (8, True, "xla_flash"), (8, False, "xla_flash"),
])
def test_scan_equals_dense(setup, u, offload, impl):
    cfg, p, x, do = setup
    _assert_matches_dense(cfg, p, x, do, u, offload, impl)


def test_scan_equals_dense_windowed(setup):
    cfg, p, x, do = setup
    _assert_matches_dense(cfg, p, x, do, 4, True, "xla_flash", window=12)


@pytest.mark.parametrize("impl,window", [("pallas", 0), ("xla_flash", 0), ("xla_flash", 12)])
def test_baseline_equals_dense(setup, impl, window):
    """u = 1: the plain baseline's one diagonal pair, forward and backward."""
    cfg, p, x, do = setup
    _assert_matches_dense(cfg, p, x, do, 1, False, impl, window=window)


@pytest.mark.parametrize("u,seq,window,sparsity,impl", [
    (3, 48, 0, 0.0, "xla_flash"),   # odd u: one query chunk per step
    (6, 48, 0, 0.0, "pallas"),      # even, not a power of two: pairs
    (4, 32, 5, 0.0, "xla_flash"),   # window: chunk 3 skips KV chunk 1, chunk 2 needs it
    (4, 32, 1, 0.0, "xla_flash"),   # window 1: the pair (2m+1, 2m) is skipped too
    (8, 32, 9, 0.0, "xla_flash"),
    (8, 32, 0, 0.5, "xla_flash"),   # sparsity stride 2: the pair's live KV chunks differ
    (8, 32, 0, 0.67, "xla_flash"),  # sparsity stride 3
])
def test_grouped_forward_equals_dense(setup, u, seq, window, sparsity, impl):
    """The scan forward takes query chunks two at a time when u is even (one
    fetch of each KV chunk serves both), one at a time when u is odd; under
    window and sparsity schedules each query runs only its own live pairs.
    Outputs and every grad equal the dense reference's."""
    cfg, p, _, _ = setup
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (2, seq, cfg.d_model), jnp.float32)
    do = jax.random.normal(jax.random.fold_in(key, 1), (2, seq, cfg.q_dim), jnp.float32)
    if sparsity:
        assert fpdt.sparsity_stride(sparsity) in (2, 3)
    _assert_matches_dense(cfg, p, x, do, u, True, impl, window=window, sparsity=sparsity)


@pytest.mark.parametrize("u", [2, 3, 4, 6])
def test_overlapped_path_equals_dense(setup, u):
    """A dense schedule with the Pallas kernels takes the overlapped path:
    each chunk pair's kernels in a loop over two head groups
    (``fpdt.head_groups``), the last chunk's prefetch not issued, and no
    ``lax.cond`` but one around each chunk loop, which skips an empty range
    (the forward's KV loop, Fig. 7's q loop).  Outputs and every grad equal
    the dense reference's; a window puts a ``lax.cond`` around each pair."""
    cfg, p, _, _ = setup
    assert fpdt.head_groups(ParallelContext(mesh=None), "local", cfg.num_kv_heads) == 2
    key = jax.random.PRNGKey(13)
    seq = 8 * u
    x = jax.random.normal(key, (2, seq, cfg.d_model), jnp.float32)
    do = jax.random.normal(jax.random.fold_in(key, 1), (2, seq, cfg.q_dim), jnp.float32)
    _assert_matches_dense(cfg, p, x, do, u, True, "pallas")

    def conds(window):  # lax.cond outside the kernels' own bodies
        c = dataclasses.replace(cfg, fpdt_chunks=u, fpdt_offload=True, block_q=8, block_k=8)
        par = ParallelContext(mesh=None, attn_impl="pallas")
        return _primitives(jax.make_jaxpr(jax.grad(lambda x: fpdt.fpdt_attention(
            c, par, p, x, kind="local", window=window).sum()))(x).jaxpr).count("cond")

    assert conds(0) == 2 and conds(12) > 2


def _primitives(jaxpr):
    """Primitive names of a jaxpr and the jaxprs nested in it, except inside
    ``pallas_call`` (a kernel's own control flow)."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _primitives(sub)
    return out


def test_scan_equals_baseline(setup):
    """Transitivity anchor: scan path vs the u=1 un-chunked baseline."""
    cfg, p, x, do = setup
    o1, g1 = _run(cfg, p, x, do, 1, False, "xla_flash")
    o, g = _run(cfg, p, x, do, 4, True, "xla_flash")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o1), rtol=2e-4, atol=2e-4)
    _assert_trees_match(g, g1, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# sparse schedules: skipped chunk pairs, zero-grad correctness
# ---------------------------------------------------------------------------


def _dense_sparse_reference(cfg, p, x, do, u, window, sparsity):
    """Oracle: materialized attention under the exact token mask the FPDT
    sparse schedule implements (causal & window & pair_live block mask)."""
    b, S, _ = x.shape
    cq = S // u
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qpos = np.arange(S)[:, None]
    kpos = np.arange(S)[None, :]
    ok = qpos >= kpos
    if window:
        ok = ok & (qpos - kpos < window)
    blk = np.zeros((S, S), bool)
    for i in range(u):
        for j in range(u):
            if fpdt.pair_live(i, j, cq=cq, window=window, sparsity=sparsity):
                blk[i * cq:(i + 1) * cq, j * cq:(j + 1) * cq] = True
    mask = jnp.asarray(ok & blk)

    def f(x, p):
        q, k, v = L.qkv_proj(cfg, p, x)
        pos = jnp.arange(S)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        q = q.transpose(0, 2, 1, 3).astype(jnp.float32)
        k = k.transpose(0, 2, 1, 3).astype(jnp.float32)
        v = v.transpose(0, 2, 1, 3).astype(jnp.float32)
        if hkv != hq:
            k = jnp.repeat(k, hq // hkv, axis=1)
            v = jnp.repeat(v, hq // hkv, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
        s = jnp.where(mask, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", pr, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, S, hq * dh)
        return (o * do).sum(), o

    (_, o), grads = jax.jit(
        jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(x, p)
    return o, grads


def test_sparse_skipped_chunks_grads(setup):
    """attn_sparsity=0.5, u=8: pair_live skips diagonal-adjacent-but-one
    chunks (j = i-2, i-4, ...).  Outputs AND grads must match the dense
    masked-attention oracle — in particular dk/dv receive exactly zero from
    skipped pairs and dq stays finite on every chunk."""
    cfg, p, x, do = setup
    u, sparsity = 8, 0.5
    cq = x.shape[1] // u
    # the schedule really skips pairs (otherwise this test is vacuous)
    assert not fpdt.pair_live(4, 2, cq=cq, window=0, sparsity=sparsity)
    assert fpdt.pair_live(4, 3, cq=cq, window=0, sparsity=sparsity)
    o, g = _run(cfg, p, x, do, u, True, "xla_flash", sparsity=sparsity)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
    o_ref, g_ref = _dense_sparse_reference(cfg, p, x, do, u, 0, sparsity)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=2e-4, atol=2e-4)
    _assert_trees_match(g, g_ref, rtol=5e-4, atol=5e-4)
