"""Pallas TPU flash-attention kernels with FPDT chunk-carry support.

Design (TPU-native):
  * Layout [b, h, s, d]; grid (b, h, num_q_blocks, num_k_blocks) with the
    k-block dimension innermost and sequential ("arbitrary"), carrying the
    online-softmax state (m, l, acc) in fp32 VMEM scratch.
  * BlockSpec tiles: q (block_q, d), k/v (block_k, d) — d is the MXU lane
    dim (64/128/256 in our archs); block_q/block_k default 512 so a tile set
    (q + k + v + acc + p) stays well under VMEM (~4 MB at d=128, bf16 in /
    fp32 accum).
  * Per-row softmax state (m, l, and the backward's L, delta) crosses the
    kernel boundary lane-dense, as ``[b, h, 1, s]`` rows tiled (1, block_q),
    so every block's last two dims are legal Mosaic tiles.  Inside a kernel
    a row becomes a lane-replicated ``(block_q, 128)`` column (one XLU
    transpose per q tile, at init/write) wherever it must broadcast across
    a score row; the dkv kernel works on transposed scores ``[block_k,
    block_q]`` so its rows broadcast over sublanes with no transpose at all.
  * Carry-in (acc, m, l) inputs let the FPDT sequence-chunk pipeline continue
    one softmax across chunk boundaries; outputs are the *unnormalized*
    running state, normalized once per chunk row at the JAX level.
  * Causal masking against *global* positions: q_offset/k_offset arrive as a
    scalar-prefetch operand (SMEM), so they may be *traced* values — the
    scan-compiled FPDT pipeline calls one kernel instance with loop-carried
    chunk offsets instead of unrolling u**2 staticly-offset copies.  Dead
    (fully-masked) blocks are still skipped with @pl.when on a predicate
    computed from the prefetched offsets.
  * GQA is native: k/v index maps fold the q-head -> kv-head group mapping;
    the dkv backward kernel accumulates over the q heads of each group in its
    sequential inner grid dimension.
  * ``heads=(h0, n)`` runs a kernel on q heads [h0, h0 + n) only (KV heads
    [h0 / g, (h0 + n) / g)): the inputs are read in place, h0 (traced or not)
    rides the scalar-prefetch operand beside the chunk offsets, and the
    outputs hold the n heads.  The FPDT pipeline calls the kernels of a chunk
    pair this way, one head group per trip of a loop.

On a TPU backend the kernels compile through Mosaic; elsewhere they run with
interpret=True (pure-Python execution), which is how the CPU tests run them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # TPU vreg lane width: compiled blocks are multiples of it
# Each kernel's ``pallas_call`` name.  The call pushes it onto the name stack
# (the Mosaic custom call's op_name reads ``.../flash_attention_fwd/pallas_call``)
# and XLA names the custom-call instruction after it (``%flash_attention_fwd.4``),
# so a profiler trace names each kernel.  No other instruction or scope in the
# program has a name beginning ``flash_attention``.
FWD, DQ, DKV = "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"


def _fit_block(n: int, block: int, *, tiled: bool = False) -> int:
    """Block size along an axis of length ``n`` (kernel grids need
    divisibility).  A compiled kernel (``tiled``) takes ``min(block, n)``
    and refuses it unless it divides ``n`` and is a multiple of the 128-lane
    tile; interpret mode and the XLA path shrink it to the largest divisor
    of ``n`` that is ``<= block``."""
    b = min(block, n)
    if tiled:
        if n % b or b % LANES:
            raise ValueError(
                f"block {b} over an axis of length {n} does not divide it "
                f"into multiples of the {LANES}-lane TPU tile")
        return b
    while n % b:
        b -= 1
    return b


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _offsets_operand(q_offset, k_offset, heads=None) -> jnp.ndarray:
    """[q_offset, k_offset] (then the first head h0 under ``heads``) as the
    int32 scalar-prefetch operand.

    Accepts Python ints (the u = 1 baseline: offsets are trace-time constants)
    and traced int scalars (scan-compiled FPDT: offsets are loop carries).
    """
    offs = (q_offset, k_offset) + (() if heads is None else (heads[0],))
    return jnp.stack([jnp.asarray(o, jnp.int32) for o in offs])


def _head_window(heads, hq: int, g: int):
    """(q heads in the grid, map from a grid head index to the operand's).

    ``heads=(h0, n)``: the grid covers n q heads and head h reads operand
    head h + h0 (from the scalar-prefetch operand); n and h0 keep whole GQA
    groups.  ``None``: every head, read where it is."""
    if heads is None:
        return hq, lambda h, offs: h
    n = heads[1]
    if n % g or hq % n:
        raise ValueError(f"a window of {n} of {hq} q heads splits a GQA group of {g}")
    return n, lambda h, offs: h + offs[2]


def _grid_spec(grid, in_specs, out_specs, scratch_shapes):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes,
    )


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )


def _rows(x: jnp.ndarray) -> jnp.ndarray:
    """[b, h, s] row state -> the kernels' lane-dense [b, h, 1, s]."""
    return x[:, :, None, :]


def _unrows(x: jnp.ndarray) -> jnp.ndarray:
    return x[:, :, 0, :]


def _row_to_col(row: jnp.ndarray) -> jnp.ndarray:
    """(1, n) lane row -> (n, 128) column, replicated across lanes."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _col_to_row(col: jnp.ndarray) -> jnp.ndarray:
    """(n, 128) lane-replicated column -> (1, n) lane row."""
    return col.T[:1, :]


def _dead(offs_ref, iq, ik, *, causal, window, block_q, block_k):
    """Block fully above the diagonal, or fully left of the window band."""
    q_start = offs_ref[0] + iq * block_q
    k_start = offs_ref[1] + ik * block_k
    dead = causal & (q_start + block_q - 1 < k_start)
    if window:
        dead = dead | (k_start + block_k - 1 < q_start - window + 1)
    return q_start, k_start, dead


def _mask(s, q_start, k_start, *, causal, window, q_axis):
    """Causal/window mask of a score tile; ``q_axis`` is the q dim of ``s``."""
    if not causal:
        return s
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    ok = qpos >= kpos
    if window:
        ok = ok & (qpos - kpos < window)
    return jnp.where(ok, s, NEG_INF)


def _nt(a, b):
    """a @ b.T with fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """a @ b with fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ===========================================================================
# Forward
# ===========================================================================


def _fwd_kernel(
    offs_ref, q_ref, k_ref, v_ref, acc_in_ref, m_in_ref, l_in_ref,
    acc_out_ref, m_out_ref, l_out_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale, causal, window, block_q, block_k, nk,
):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = _row_to_col(m_in_ref[...])
        l_scr[...] = _row_to_col(l_in_ref[...])
        acc_scr[...] = acc_in_ref[...].astype(jnp.float32)

    q_start, k_start, dead = _dead(offs_ref, iq, ik, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k)

    @pl.when(~dead)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = _nt(q, k) * sm_scale  # [block_q, block_k]
        s = _mask(s, q_start, k_start, causal=causal, window=window, q_axis=0)
        m_prev = m_scr[...]  # (block_q, 128), lane-replicated
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit mask (don't rely on exp underflow of NEG_INF - NEG_INF)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, :1]))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _nn(p, v)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _write():
        acc_out_ref[...] = acc_scr[...]
        m_out_ref[...] = _col_to_row(m_scr[...])
        l_out_ref[...] = _col_to_row(l_scr[...])


def flash_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    carry: Optional[tuple] = None,  # (acc [b,h,sq,d] f32, m [b,h,sq] f32, l f32)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    k_offset: int = 0,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    heads: Optional[tuple] = None,
):
    """Unnormalized online attention of q (at q_offset) over k/v (at k_offset).

    Returns (acc, m, l): fp32 running state (continuing ``carry`` if given),
    of the q heads ``heads=(h0, n)`` selects (all by default).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    nh, qh = _head_window(heads, hq, g)
    interpret = _default_interpret() if interpret is None else interpret
    block_q = _fit_block(sq, block_q, tiled=not interpret)
    block_k = _fit_block(sk, block_k, tiled=not interpret)
    nq, nk = sq // block_q, sk // block_k
    scale = sm_scale if sm_scale is not None else d ** -0.5

    if carry is None:
        acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
        m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq, sq), jnp.float32)
    else:
        acc0, m0, l0 = carry

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    grid = (b, nh, nq, nk)
    q_spec = pl.BlockSpec((None, None, block_q, d), lambda b_, h, iq, ik, offs: (b_, qh(h, offs), iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d), lambda b_, h, iq, ik, offs: (b_, qh(h, offs) // g, ik, 0))
    row_spec = pl.BlockSpec((None, None, 1, block_q), lambda b_, h, iq, ik, offs: (b_, qh(h, offs), 0, iq))
    if heads is None:
        q_out, row_out = q_spec, row_spec
    else:
        q_out = pl.BlockSpec((None, None, block_q, d), lambda b_, h, iq, ik, offs: (b_, h, iq, 0))
        row_out = pl.BlockSpec((None, None, 1, block_q), lambda b_, h, iq, ik, offs: (b_, h, 0, iq))
    row_shape = jax.ShapeDtypeStruct((b, nh, 1, sq), jnp.float32)

    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            grid,
            [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            [q_out, row_out, row_out],
            [
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, nh, sq, d), jnp.float32), row_shape, row_shape],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=FWD,
    )(_offsets_operand(q_offset, k_offset, heads), q, k, v, acc0, _rows(m0), _rows(l0))
    return acc, _unrows(m), _unrows(l)


# ===========================================================================
# Backward: dq
# ===========================================================================


def _dq_kernel(
    offs_ref, q_ref, k_ref, v_ref, do_ref, L_ref, delta_ref,
    dq_ref,
    dq_scr, L_scr, delta_scr,
    *, sm_scale, causal, window, block_q, block_k, nk,
):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        L_scr[...] = _row_to_col(L_ref[...])
        delta_scr[...] = _row_to_col(delta_ref[...])

    q_start, k_start, dead = _dead(offs_ref, iq, ik, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k)

    @pl.when(~dead)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        s = _nt(q, k) * sm_scale
        s = _mask(s, q_start, k_start, causal=causal, window=window, q_axis=0)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - L_scr[...][:, :1]))
        ds = p * (_nt(do, v) - delta_scr[...][:, :1]) * sm_scale
        dq_scr[...] += _nn(ds, k)

    @pl.when(ik == nk - 1)
    def _write():
        dq_ref[...] = dq_scr[...]


def flash_bwd_dq(
    q, k, v, do, L, delta,
    *, causal=True, window=0, q_offset=0, k_offset=0, sm_scale=None,
    block_q=512, block_k=512, interpret=None, heads=None,
):
    """dq contribution of this (q-chunk, kv-chunk) pair, of the q heads
    ``heads=(h0, n)`` selects (all by default). fp32 output."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    nh, qh = _head_window(heads, hq, g)
    interpret = _default_interpret() if interpret is None else interpret
    block_q = _fit_block(sq, block_q, tiled=not interpret)
    block_k = _fit_block(sk, block_k, tiled=not interpret)
    nq, nk = sq // block_q, sk // block_k
    scale = sm_scale if sm_scale is not None else d ** -0.5

    kernel = functools.partial(
        _dq_kernel, sm_scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    q_spec = pl.BlockSpec((None, None, block_q, d), lambda b_, h, iq, ik, offs: (b_, qh(h, offs), iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d), lambda b_, h, iq, ik, offs: (b_, qh(h, offs) // g, ik, 0))
    row_spec = pl.BlockSpec((None, None, 1, block_q), lambda b_, h, iq, ik, offs: (b_, qh(h, offs), 0, iq))
    q_out = q_spec if heads is None else pl.BlockSpec(
        (None, None, block_q, d), lambda b_, h, iq, ik, offs: (b_, h, iq, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            (b, nh, nq, nk),
            [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            q_out,
            [pltpu.VMEM((block_q, d), jnp.float32),
             pltpu.VMEM((block_q, LANES), jnp.float32),
             pltpu.VMEM((block_q, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nh, sq, d), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=DQ,
    )(_offsets_operand(q_offset, k_offset, heads), q, k, v, do, _rows(L), _rows(delta))


# ===========================================================================
# Backward: dk, dv
# ===========================================================================


def _dkv_kernel(
    offs_ref, q_ref, k_ref, v_ref, do_ref, L_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, sm_scale, causal, window, block_q, block_k, nq, g,
):
    ik = pl.program_id(2)
    t = pl.program_id(3)  # runs over g * nq (q heads of the group x q blocks)
    iq = t % nq

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start, k_start, dead = _dead(offs_ref, iq, ik, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k)

    @pl.when(~dead)
    def _compute():
        # transposed scores [block_k, block_q]: the (1, block_q) rows L and
        # delta broadcast over sublanes, and every matmul is NN or NT
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        st = _nt(k, q) * sm_scale
        st = _mask(st, q_start, k_start, causal=causal, window=window, q_axis=1)
        pt = jnp.where(st <= NEG_INF / 2, 0.0, jnp.exp(st - L_ref[...]))
        dv_scr[...] += _nn(pt, do)
        dst = pt * (_nt(v, do) - delta_ref[...]) * sm_scale
        dk_scr[...] += _nn(dst, q)

    @pl.when(t == g * nq - 1)
    def _write():
        dk_ref[...] = dk_scr[...]
        dv_ref[...] = dv_scr[...]


def flash_bwd_dkv(
    q, k, v, do, L, delta,
    *, causal=True, window=0, q_offset=0, k_offset=0, sm_scale=None,
    block_q=512, block_k=512, interpret=None, heads=None,
):
    """(dk, dv) contribution of this (q-chunk, kv-chunk) pair (GQA-summed),
    of the KV heads of the q heads ``heads=(h0, n)`` selects (all by default)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    nh, qh = _head_window(heads, hq, g)
    # the grid runs over KV heads: a window starts at KV head h0 / g
    kh = (lambda h, offs: h) if heads is None else (lambda h, offs: h + offs[2] // g)
    interpret = _default_interpret() if interpret is None else interpret
    block_q = _fit_block(sq, block_q, tiled=not interpret)
    block_k = _fit_block(sk, block_k, tiled=not interpret)
    nq, nk = sq // block_q, sk // block_k
    scale = sm_scale if sm_scale is not None else d ** -0.5

    kernel = functools.partial(
        _dkv_kernel, sm_scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nq=nq, g=g,
    )
    # inner sequential dim covers q heads of the kv group x q blocks
    q_spec = pl.BlockSpec(
        (None, None, block_q, d), lambda b_, h, ik, t, offs: (b_, kh(h, offs) * g + t // nq, t % nq, 0)
    )
    kv_spec = pl.BlockSpec((None, None, block_k, d), lambda b_, h, ik, t, offs: (b_, kh(h, offs), ik, 0))
    row_spec = pl.BlockSpec(
        (None, None, 1, block_q), lambda b_, h, ik, t, offs: (b_, kh(h, offs) * g + t // nq, 0, t % nq)
    )
    kv_out = kv_spec if heads is None else pl.BlockSpec(
        (None, None, block_k, d), lambda b_, h, ik, t, offs: (b_, h, ik, 0))
    nkv = nh // g
    return pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            (b, nkv, nk, g * nq),
            [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            [kv_out, kv_out],
            [pltpu.VMEM((block_k, d), jnp.float32), pltpu.VMEM((block_k, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, nkv, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, nkv, sk, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name=DKV,
    )(_offsets_operand(q_offset, k_offset, heads), q, k, v, do, _rows(L), _rows(delta))
