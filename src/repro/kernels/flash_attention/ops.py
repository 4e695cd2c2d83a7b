"""Jit-ready flash-attention ops.

Three implementations with identical semantics (cross-checked in tests):
  * impl="pallas"    — the Pallas TPU kernels (interpret=True off-TPU).
  * impl="xla_flash" — jnp blockwise online-softmax (lax.scan over KV blocks,
                       O(seq) memory, custom recompute backward).  Used by the
                       512-device dry-run (Pallas doesn't lower on the CPU
                       backend) and as a portable fallback.
  * impl="ref"       — exact materialized attention (tiny tests only).

All expose the chunk-level primitives FPDT schedules:
  chunk_fwd      (q_i, kv_j, carry) -> running (acc, m, l)
  chunk_bwd_dq   per-pair dq contribution given final row LSE + delta
  chunk_bwd_dkv  per-pair (dk, dv) contribution
plus ``flash_attention`` — a fused single-call attention with custom VJP.

``q_offset``/``k_offset`` may be Python ints (the u = 1 baseline) or *traced*
int scalars (the scan-compiled pipeline passes loop-carried chunk offsets):
the xla/ref paths consume them as ordinary values and the Pallas kernels
take them as a scalar-prefetch operand.  Shapes and block sizes stay
static.  ``heads=(h0, n)`` (Pallas only) runs a chunk primitive on q heads
[h0, h0 + n) of its operands, read in place; its outputs hold those heads.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.online_softmax import NEG_INF, SoftmaxState, finalize, lse
from repro.kernels.flash_attention import kernel as _k
from repro.kernels.flash_attention import ref as _ref

# ---------------------------------------------------------------------------
# XLA blockwise implementation
# ---------------------------------------------------------------------------


def _xla_chunk_fwd(q, k, v, carry, *, causal, window, q_offset, k_offset, sm_scale, block_k):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    k = _ref._expand_kv(k, hq)
    v = _ref._expand_kv(v, hq)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    block_k = _k._fit_block(sk, block_k)
    nk = sk // block_k
    kb = k.reshape(b, hq, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hq, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    qf = q.astype(jnp.float32)
    qpos = q_offset + jnp.arange(sq)[:, None]

    def step(state, inp):
        j, kj, vj = inp
        acc, m, l = state
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32)) * scale
        if causal:
            kpos = k_offset + j * block_k + jnp.arange(block_k)[None, :]
            ok = qpos >= kpos
            if window:
                ok = ok & (qpos - kpos < window)
            s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vj.astype(jnp.float32))
        return (acc, m_new, l), None

    if carry is None:
        acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
        m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq, sq), jnp.float32)
        carry = (acc0, m0, l0)
    (acc, m, l), _ = jax.lax.scan(step, tuple(carry), (jnp.arange(nk), kb, vb))
    return acc, m, l


def _xla_chunk_bwd_dq(q, k, v, do, L, delta, *, causal, window, q_offset, k_offset, sm_scale, block_k):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    k = _ref._expand_kv(k, hq)
    v = _ref._expand_kv(v, hq)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    block_k = _k._fit_block(sk, block_k)
    nk = sk // block_k
    kb = k.reshape(b, hq, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hq, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    qpos = q_offset + jnp.arange(sq)[:, None]

    def step(dq, inp):
        j, kj, vj = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32)) * scale
        if causal:
            kpos = k_offset + j * block_k + jnp.arange(block_k)[None, :]
            ok = qpos >= kpos
            if window:
                ok = ok & (qpos - kpos < window)
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - L[..., None]))
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        return dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kj.astype(jnp.float32)), None

    dq0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    dq, _ = jax.lax.scan(step, dq0, (jnp.arange(nk), kb, vb))
    return dq


def _xla_chunk_bwd_dkv(q, k, v, do, L, delta, *, causal, window, q_offset, k_offset, sm_scale, block_q):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    ke = _ref._expand_kv(k, hq).astype(jnp.float32)
    ve = _ref._expand_kv(v, hq).astype(jnp.float32)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    block_q = _k._fit_block(sq, block_q)
    nq = sq // block_q
    qb = q.reshape(b, hq, nq, block_q, d).transpose(2, 0, 1, 3, 4)
    dob = do.reshape(b, hq, nq, block_q, d).transpose(2, 0, 1, 3, 4)
    Lb = L.reshape(b, hq, nq, block_q).transpose(2, 0, 1, 3)
    deltab = delta.reshape(b, hq, nq, block_q).transpose(2, 0, 1, 3)
    kpos = k_offset + jnp.arange(sk)[None, :]

    def step(state, inp):
        dk, dv = state
        i, qi, doi, Li, di = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(jnp.float32), ke) * scale
        if causal:
            qpos = q_offset + i * block_q + jnp.arange(block_q)[:, None]
            ok = qpos >= kpos
            if window:
                ok = ok & (qpos - kpos < window)
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - Li[..., None]))
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, doi.astype(jnp.float32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", doi.astype(jnp.float32), ve)
        ds = p * (dp - di[..., None]) * scale
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qi.astype(jnp.float32))
        return (dk, dv), None

    z = jnp.zeros((b, hq, sk, d), jnp.float32)
    (dk, dv), _ = jax.lax.scan(step, (z, z), (jnp.arange(nq), qb, dob, Lb, deltab))
    if g > 1:  # GQA: sum the q-head group
        dk = dk.reshape(b, hkv, g, sk, d).sum(2)
        dv = dv.reshape(b, hkv, g, sk, d).sum(2)
    return dk, dv


# ---------------------------------------------------------------------------
# Dispatchers (chunk-level primitives used by FPDT)
# ---------------------------------------------------------------------------


def chunk_fwd(q, k, v, carry=None, *, causal=True, window=0, q_offset=0, k_offset=0,
              sm_scale=None, block_q=512, block_k=512, impl="pallas", heads=None):
    if impl == "pallas":
        return _k.flash_fwd(q, k, v, carry, causal=causal, window=window,
                            q_offset=q_offset, k_offset=k_offset, sm_scale=sm_scale,
                            block_q=block_q, block_k=block_k, heads=heads)
    assert heads is None, "a head window needs impl='pallas'"
    if impl == "xla_flash":
        return _xla_chunk_fwd(q, k, v, carry, causal=causal, window=window,
                              q_offset=q_offset, k_offset=k_offset,
                              sm_scale=sm_scale, block_k=block_k)
    st = _ref.attend_chunk(q, k, v, causal=causal, window=window, q_offset=q_offset,
                           k_offset=k_offset, sm_scale=sm_scale,
                           carry=SoftmaxState(*carry) if carry is not None else None)
    return tuple(st)


def chunk_bwd_dq(q, k, v, do, L, delta, *, causal=True, window=0, q_offset=0, k_offset=0,
                 sm_scale=None, block_q=512, block_k=512, impl="pallas", heads=None):
    if impl == "pallas":
        return _k.flash_bwd_dq(q, k, v, do, L, delta, causal=causal, window=window,
                               q_offset=q_offset, k_offset=k_offset, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k, heads=heads)
    assert heads is None, "a head window needs impl='pallas'"
    return _xla_chunk_bwd_dq(q, k, v, do, L, delta, causal=causal, window=window,
                             q_offset=q_offset, k_offset=k_offset, sm_scale=sm_scale,
                             block_k=block_k)


def chunk_bwd_dkv(q, k, v, do, L, delta, *, causal=True, window=0, q_offset=0, k_offset=0,
                  sm_scale=None, block_q=512, block_k=512, impl="pallas", heads=None):
    if impl == "pallas":
        return _k.flash_bwd_dkv(q, k, v, do, L, delta, causal=causal, window=window,
                                q_offset=q_offset, k_offset=k_offset, sm_scale=sm_scale,
                                block_q=block_q, block_k=block_k, heads=heads)
    assert heads is None, "a head window needs impl='pallas'"
    return _xla_chunk_bwd_dkv(q, k, v, do, L, delta, causal=causal, window=window,
                              q_offset=q_offset, k_offset=k_offset, sm_scale=sm_scale,
                              block_q=block_q)


# ---------------------------------------------------------------------------
# Fused single-call attention with custom VJP
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_flash(causal, window, sm_scale, block_q, block_k, impl):
    kw = dict(causal=causal, window=window, sm_scale=sm_scale, block_q=block_q,
              block_k=block_k, impl=impl)

    @jax.custom_vjp
    def f(q, k, v):
        acc, m, l = chunk_fwd(q, k, v, **kw)
        return finalize(SoftmaxState(acc, m, l)).astype(q.dtype)

    def f_fwd(q, k, v):
        acc, m, l = chunk_fwd(q, k, v, **kw)
        o = finalize(SoftmaxState(acc, m, l))
        L = lse(SoftmaxState(acc, m, l))
        return o.astype(q.dtype), (q, k, v, o, L)

    def f_bwd(res, do):
        q, k, v, o, L = res
        dof = do.astype(jnp.float32)
        delta = jnp.sum(dof * o, axis=-1)
        dq = chunk_bwd_dq(q, k, v, dof, L, delta, **kw)
        dk, dv = chunk_bwd_dkv(q, k, v, dof, L, delta, **kw)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f


def flash_attention(q, k, v, *, causal=True, window=0, sm_scale=None,
                    block_q=512, block_k=512, impl="pallas"):
    """Fused causal flash attention [b, h, s, d] with custom VJP (GQA-aware)."""
    if impl == "ref":
        return _ref.mha(q, k, v, causal=causal, window=window, sm_scale=sm_scale)
    return _make_flash(causal, window, sm_scale, block_q, block_k, impl)(q, k, v)
