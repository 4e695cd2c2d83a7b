"""Pallas flash-attention kernels vs the pure-jnp oracle: shape/dtype sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.online_softmax import SoftmaxState, finalize, lse
from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ops as O
from repro.kernels.flash_attention import ref as R


def _mk(rng, b, hq, hkv, sq, sk, d, dtype):
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    return q, k, v


SWEEP = [
    # b, hq, hkv, sq, sk, d, block
    (1, 1, 1, 16, 16, 8, 8),
    (2, 4, 2, 32, 32, 16, 16),
    (1, 4, 1, 64, 64, 32, 16),   # MQA
    (1, 3, 3, 48, 48, 16, 16),   # odd head count, non-divisible block fit
    (2, 2, 2, 40, 24, 16, 8),    # sq != sk
]


@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,blk", SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwd_matches_ref(rng, b, hq, hkv, sq, sk, d, blk, dtype):
    q, k, v = _mk(rng, b, hq, hkv, sq, sk, d, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    want = R.mha(*(x.astype(jnp.float32) for x in (q, k, v)), causal=True)
    got = O.flash_attention(q, k, v, impl="pallas", block_q=blk, block_k=blk)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,blk", SWEEP[:3])
def test_bwd_matches_autodiff_ref(rng, b, hq, hkv, sq, sk, d, blk):
    q, k, v = _mk(rng, b, hq, hkv, sq, sk, d, jnp.float32)

    def loss_ref(q, k, v):
        return (R.mha(q, k, v, causal=True) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for impl in ("pallas", "xla_flash"):
        def loss_k(q, k, v):
            return (O.flash_attention(q, k, v, impl=impl, block_q=blk, block_k=blk) ** 2).sum()

        g = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))(q, k, v)
        for a, b_ in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4)


def test_chunk_carry_continues_softmax(rng):
    b, h, s, d = 1, 2, 64, 16
    q, k, v = _mk(rng, b, h, h, s, s, d, jnp.float32)
    want = R.mha(q, k, v, causal=True)
    cq = s // 4
    outs = []
    for i in range(4):
        qi = q[:, :, i * cq:(i + 1) * cq]
        carry = None
        for j in range(i + 1):
            carry = K.flash_fwd(qi, k[:, :, j * cq:(j + 1) * cq], v[:, :, j * cq:(j + 1) * cq],
                                carry, causal=True, q_offset=i * cq, k_offset=j * cq,
                                block_q=16, block_k=16)
        outs.append(finalize(SoftmaxState(*carry)))
    got = jnp.concatenate(outs, axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [4, 16, 33])
def test_window(rng, window):
    b, h, s, d = 1, 2, 48, 16
    q, k, v = _mk(rng, b, h, h, s, s, d, jnp.float32)
    sc = d ** -0.5
    sm = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sc
    qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = (qp >= kp) & (qp - kp < window)
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(mask, sm, -1e30), axis=-1), v)
    for impl in ("pallas", "xla_flash", "ref"):
        got = O.flash_attention(q, k, v, causal=True, window=window, impl=impl,
                                block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_causality_property(rng):
    """Output at position i must not depend on tokens after i."""
    b, h, s, d = 1, 2, 32, 8
    q, k, v = _mk(rng, b, h, h, s, s, d, jnp.float32)
    base = O.flash_attention(q, k, v, impl="pallas", block_q=8, block_k=8)
    k2 = k.at[:, :, 20:].set(99.0)
    v2 = v.at[:, :, 20:].set(-99.0)
    pert = O.flash_attention(q, k2, v2, impl="pallas", block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(base[:, :, :20]), np.asarray(pert[:, :, :20]),
                               rtol=1e-6, atol=1e-6)
    assert not np.allclose(np.asarray(base[:, :, 21:]), np.asarray(pert[:, :, 21:]))


@pytest.mark.parametrize("hq,hkv,n", [(4, 2, 2), (4, 4, 1), (8, 2, 4), (4, 1, 4)])
def test_head_window_equals_full_call(rng, hq, hkv, n):
    """``heads=(h0, n)``: each kernel run on a window of q heads (h0 traced)
    returns exactly those heads of the full call, GQA groups kept whole."""
    b, sq, d = 2, 32, 16
    q, k, v = _mk(rng, b, hq, hkv, sq, sq, d, jnp.float32)
    do = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
    acc0 = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
    m0 = jnp.asarray(rng.standard_normal((b, hq, sq)), jnp.float32)
    l0 = jnp.asarray(rng.uniform(1.0, 2.0, (b, hq, sq)), jnp.float32)
    L = jnp.asarray(rng.standard_normal((b, hq, sq)), jnp.float32)
    delta = jnp.asarray(rng.standard_normal((b, hq, sq)), jnp.float32)
    kw = dict(causal=True, q_offset=32, k_offset=16, block_q=8, block_k=8)
    g = hq // hkv

    def calls(heads):
        return (K.flash_fwd(q, k, v, (acc0, m0, l0), heads=heads, **kw),
                K.flash_bwd_dq(q, k, v, do, L, delta, heads=heads, **kw),
                K.flash_bwd_dkv(q, k, v, do, L, delta, heads=heads, **kw))

    full = calls(None)
    win = jax.jit(lambda h0: calls((h0, n)))
    for h0 in range(0, hq, n):
        for got, want in zip(jax.tree.leaves(win(h0)), jax.tree.leaves(full)):
            sl = slice(h0, h0 + n) if got.shape[1] == n else slice(h0 // g, (h0 + n) // g)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want[:, sl]))
    with pytest.raises(ValueError, match="GQA group"):  # half a group, or 3 of 4 heads
        K.flash_fwd(q, k, v, heads=(0, g // 2 if g > 1 else 3), **kw)
