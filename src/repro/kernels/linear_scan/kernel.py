"""Pallas TPU blocked linear-scan kernel: h_t = a_t * h_{t-1} + b_t.

Used by the Mamba selective scan (channels = d_inner * d_state) and the
RG-LRU recurrence (channels = lru_width).  TPU mapping:
  * layout [batch, seq, chan], chan on the 128-lane axis, seq on sublanes;
  * grid (batch, chan_blocks, seq_blocks), seq innermost & sequential,
    carrying the running state h [block_c] in fp32 VMEM scratch;
  * within a block the inclusive scan is a log-step (Hillis-Steele) scan:
    log2(block_s) rounds of a sublane roll (``pltpu.roll``) and a masked
    multiply-add over the whole tile, not a serial per-timestep loop — the
    VPU stays fully occupied;
  * cross-block composition uses the scanned pair (A_cum, B_cum):
    h_block = B_cum + A_cum * h_carry.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _scan_kernel(a_ref, b_ref, h0_ref, o_ref, h_scr, *, block_s):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    A = a_ref[...].astype(jnp.float32)  # [block_s, block_c]
    B = b_ref[...].astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, A.shape, 0)
    shift = 1
    while shift < block_s:
        # compose (A, B)[t - shift] into (A, B)[t]; rows above the block's
        # start compose with the identity (1, 0)
        keep = row >= shift
        A_sh = jnp.where(keep, pltpu.roll(A, shift, 0), 1.0)
        B_sh = jnp.where(keep, pltpu.roll(B, shift, 0), 0.0)
        B = B + A * B_sh
        A = A * A_sh
        shift *= 2
    out = B + A * h_scr[...]
    o_ref[...] = out.astype(o_ref.dtype)
    h_scr[...] = out[block_s - 1:, :]


def linear_scan(
    a: jnp.ndarray,
    b: jnp.ndarray,
    h0: Optional[jnp.ndarray] = None,
    *,
    block_s: int = 256,
    block_c: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """All inclusive states of h_t = a_t h_{t-1} + b_t. fp32 output."""
    bsz, seq, chan = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, chan), jnp.float32)
    block_s = min(block_s, seq)
    block_c = min(block_c, chan)
    assert seq % block_s == 0 and chan % block_c == 0, (seq, block_s, chan, block_c)
    ns, nc = seq // block_s, chan // block_c
    interpret = _default_interpret() if interpret is None else interpret
    if not interpret and (block_s % 8 or block_c % 128):
        raise ValueError(
            f"linear_scan block ({block_s}, {block_c}) over [{bsz}, {seq}, "
            f"{chan}] is not a multiple of the (8, 128) TPU tile")

    return pl.pallas_call(
        functools.partial(_scan_kernel, block_s=block_s),
        grid=(bsz, nc, ns),
        in_specs=[
            pl.BlockSpec((None, block_s, block_c), lambda b_, ic, is_: (b_, is_, ic)),
            pl.BlockSpec((None, block_s, block_c), lambda b_, ic, is_: (b_, is_, ic)),
            pl.BlockSpec((None, 1, block_c), lambda b_, ic, is_: (b_, 0, ic)),
        ],
        out_specs=pl.BlockSpec((None, block_s, block_c), lambda b_, ic, is_: (b_, is_, ic)),
        out_shape=jax.ShapeDtypeStruct((bsz, seq, chan), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b, h0[:, None, :])
