"""FPDT: the paper's sequence-chunk pipelined distributed attention.

The chunk count u alone picks one of two builders:

  * ``u = 1``     -> the paper's baseline, plain DeepSpeed-Ulysses (or CP)
                     attention: project, all-to-all, one flash-attention
                     call, all-to-all back; its custom VJP runs the one
                     pair's backward kernels.  No chunk store, no offload,
                     no loop.
  * ``u > 1``     -> the FPDT pipeline: the hidden chunk T_i is projected to
                     (q_i, k_i, v_i), per-chunk all-to-all scatters heads and
                     gathers sequence (GSPMD reshard induced by a sharding
                     constraint), online attention continues one softmax
                     across KV chunks j <= i, and idle KV chunks are
                     offloaded to host memory and fetched back chunk-by-chunk
                     with *explicit* double buffering: the fetch of chunk
                     j+1 is issued before chunk j's kernels, and on a dense
                     schedule it is in flight while they run (below).
                     All residency decisions route through
                     ``runtime.placement.PlacementPolicy`` (a CPU without a
                     pinned-host pool degrades offload to a no-op; an
                     accelerator without one refuses it), and the pipeline
                     matches u=1 either way.

The u > 1 pipeline is compiled as loops: the forward is one ``lax.scan``
over groups of query chunks whose carry holds the KV store as preallocated
``[u, b, h, cq, dh]`` buffers (``dynamic_update_slice`` on append,
``dynamic_slice`` + placement-routed fetch on read); the inner KV loop and
the Fig. 7 backward's nested loops are ``fori_loop``s with *traced* chunk
offsets (the flash kernels take offsets as scalar-prefetch operands).
Under a window or sparsity, ``pair_live`` is a traced predicate gating each
pair with ``lax.cond`` — chunk skipping skips compute *and* host traffic
inside the compiled loop.  HLO size is O(1) in u, so u=32/u=64 schedules
(the path to the paper's 2M-token setting) trace and compile in
near-constant time (see benchmarks/compile_scaling.py).  Double buffering
carries the prefetched chunk in the loop state
(``runtime.placement.fori_double_buffered``): the fetch of chunk j+1 is
issued before chunk j's kernels in program order.

The overlapped path.  On a dense schedule (no window, no sparsity) every
pair the loops visit is live, so no ``lax.cond`` gates a pair or a fetch,
and each loop fetches its chunks exactly once, the last without a prefetch
after it (the loop runs under one ``lax.cond`` that skips an empty range:
the forward's first query group has no KV chunk before it).  XLA's TPU
scheduler overlaps a host fetch in flight with a loop but not with Mosaic
calls beside it in the same computation, so on one device each chunk
pair's Pallas kernels run as a loop over T = 2 groups of heads
(``head_groups``): the kernels read their head window in place and the
group's outputs are written into the running state.  The fetch of the next
chunk then starts before that loop and is waited on after it.  Each loop's
first fetch stays exposed (``overlapped_fetches`` counts the others).

Query chunks go through the scan forward two at a time when u is even
(``fwd_group``).  Chunks 2m and 2m+1 both attend KV chunks 0..2m-1, so the
forward fetches each of those once and runs both query chunks' kernels on
it, each on its own softmax state and only where its own pair is live; a
chunk is fetched when either pair is (``kv_fetch_live``).  KV chunk 2m is
taken from the device, where it was just projected.  Each query chunk
still meets its KV chunks in the same order, so the result is the
one-chunk schedule's, while the host link carries each KV chunk half as
often: u(u-2)/4 fetches a layer in place of u(u-1)/2 (``fwd_kv_fetches``:
56 in place of 120 at u=16).  An odd u keeps one query chunk per step.

Backward is a custom VJP implementing the paper's Fig. 7 nested loop:
outer loop over KV chunks j, inner loop over query chunks i >= j, using the
saved final row-LSE L_i so every (i, j) pair's contribution is independent:
dk_j/dv_j accumulate across the inner loop; dq_i accumulates across outer
iterations and finalizes when j == i, at which point it is all-to-all'd back
(a sharding constraint) and back-projected — overlapping with the next KV
chunk's fetch, exactly the paper's schedule.

Distribution modes:
  * kind="ulysses" — heads % sp == 0 (KV heads fewer than sp stay
                     replicated over the model axis);
  * kind="cp"      — all-gather context parallelism for archs whose head
                     count doesn't divide the model axis; with u > 1 this
                     becomes chunk-streamed KV all-gather + offload
                     ("FPDT-CP", a beyond-paper generalization);
  * kind="local"   — single-device / no model axis.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.core.online_softmax import SoftmaxState, finalize, lse, zero_state_like
from repro.core.parallel import ParallelContext
from repro.kernels.flash_attention import ops as fa
from repro.models.layers import apply_rope, qkv_proj
from repro.runtime.placement import fori_double_buffered

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# sharding helpers (kind-dependent): placement of the per-chunk q/k/v
# ---------------------------------------------------------------------------


def _shard_q(par: ParallelContext, kind: str, q: jnp.ndarray) -> jnp.ndarray:
    """q [b, s, h, d] placement inside attention."""
    if par.mesh is None or kind == "local":
        return q
    if kind == "ulysses":
        return par.head_sharded(q)  # seq gathered, heads scattered (a2a)
    return par.constrain(q, par.dp_axes, par.sp_axis, None, None)  # cp: seq stays

def _shard_q_stacked(par: ParallelContext, kind: str, x: jnp.ndarray) -> jnp.ndarray:
    """Chunk-stacked [u, b, s, h, d] variant of ``_shard_q``."""
    if par.mesh is None or kind == "local":
        return x
    if kind == "ulysses":
        return par.constrain(x, None, par.dp_axes, None, par.sp_axis, None)
    return par.constrain(x, None, par.dp_axes, par.sp_axis, None, None)


def _shard_kv(par: ParallelContext, kind: str, x: jnp.ndarray) -> jnp.ndarray:
    if par.mesh is None or kind == "local":
        return x
    if kind == "ulysses" and x.shape[2] % par.sp == 0:
        return par.head_sharded(x)
    # GQA/MQA with kv_heads < sp: keep KV replicated across the model axis
    # (a clean all-gather; never materialize a repeat + reshard, which GSPMD
    # can only realize by full rematerialization)
    return par.replicated_kv(x)


def _host_spec_kv(par: ParallelContext, kind: str, n_heads: int, chunk_len: int):
    """Sharding spec of offloaded head-layout [b, h, s, d] chunks: heads over
    model when divisible, else the chunk's sequence dim, else dp only."""
    if kind == "ulysses" and par.sp and n_heads % par.sp == 0:
        return (par.dp_axes, par.sp_axis, None, None)
    if par.sp and chunk_len % par.sp == 0:
        return (par.dp_axes, None, par.sp_axis, None)
    return (par.dp_axes, None, None, None)


def head_groups(par: ParallelContext, kind: str, n_kv_heads: int) -> int:
    """T: on a dense schedule each chunk pair runs its kernels as a loop over
    T groups of heads, each kernel reading its head window in place.  T = 2
    where one program runs the Pallas kernels on every head (no mesh) and
    the KV heads split in two, else 1 (no loop)."""
    single = par.mesh is None or kind == "local"
    return 2 if par.attn_impl == "pallas" and single and n_kv_heads % 2 == 0 else 1


def _chunk_kernels(par: ParallelContext, kind: str, impl: str, n_kv_heads: int):
    """(chunk_fwd, chunk_bwd_dq, chunk_bwd_dkv) as the pipeline calls them.

    GSPMD cannot partition a Mosaic kernel, so on a mesh where both q and KV
    heads shard over the model axis (Ulysses) each Pallas kernel runs under
    ``shard_map`` on its device's heads; GQA groups stay whole per device
    because ``hkv % sp == 0``.  Elsewhere the ops are called as they are.
    """
    if par.mesh is None or kind != "ulysses" or impl != "pallas" or n_kv_heads % par.sp:
        return fa.chunk_fwd, fa.chunk_bwd_dq, fa.chunk_bwd_dkv
    from jax.sharding import PartitionSpec as P

    heads = P(par.dp_axes, par.sp_axis, None, None)
    rows = P(par.dp_axes, par.sp_axis, None)

    def per_device(op, n_in, out_specs):
        def call(*args, q_offset, k_offset, **kw):
            def body(*a):
                return op(*a[:n_in], q_offset=a[n_in], k_offset=a[n_in + 1], **kw)

            specs = tuple(rows if a.ndim == 3 else heads for a in args[:n_in])
            return jax.shard_map(body, mesh=par.mesh, in_specs=specs + (P(), P()),
                                 out_specs=out_specs, check_vma=False)(
                *args[:n_in], jnp.asarray(q_offset, jnp.int32),
                jnp.asarray(k_offset, jnp.int32))
        return call

    fwd = per_device(lambda q, k, v, acc, m, l, **kw: fa.chunk_fwd(q, k, v, (acc, m, l), **kw),
                     6, (heads, rows, rows))

    def chunk_fwd(q, k, v, carry=None, **kw):
        carry = tuple(zero_state_like(q)) if carry is None else tuple(carry)
        return fwd(q, k, v, *carry, **kw)

    return (chunk_fwd, per_device(fa.chunk_bwd_dq, 6, heads),
            per_device(fa.chunk_bwd_dkv, 6, (heads, heads)))


# ---------------------------------------------------------------------------
# chunk-pair liveness (window band / block sparsity)
# ---------------------------------------------------------------------------


def sparsity_stride(sparsity: float) -> int:
    """Distance stride keeping ~(1-sparsity) of off-diagonal KV chunks."""
    return max(1, round(1.0 / max(1e-9, 1.0 - sparsity)))


def pair_live(i: int, j: int, *, cq: int, window: int, sparsity: float) -> bool:
    """Is the (query chunk i, KV chunk j) pair attended?  Static indices
    (a forward group's own pairs, schedules, tests); ``pair_live_traced``
    is the loop twin.  The answer depends on i - j alone."""
    if j > i:
        return False
    if window and (i - j) * cq >= window + cq - 1:
        return False  # chunk pair fully outside the attention band
    if sparsity > 0.0 and j < i:
        # block-sparse (paper §5.6): keep ~(1-sparsity) of off-diagonal
        # KV chunks by distance stride; the diagonal is always attended.
        # Fewer KV chunks are fetched from host — the paper's Table 4.
        if (i - j - 1) % sparsity_stride(sparsity) != 0:
            return False
    return True


def pair_live_traced(i, j, *, cq: int, window: int, sparsity: float):
    """Traced-predicate twin of ``pair_live`` (i/j may be int tracers).

    window/sparsity/cq stay trace-time constants — only the chunk indices
    are dynamic, so the compiled loop body carries one boolean that gates
    the pair's kernels and fetches with ``lax.cond``.
    """
    i = jnp.asarray(i, jnp.int32)
    j = jnp.asarray(j, jnp.int32)
    liv = j <= i
    if window:
        liv &= (i - j) * cq < window + cq - 1
    if sparsity > 0.0:
        liv &= (j == i) | ((i - j - 1) % sparsity_stride(sparsity) == 0)
    return liv


def fwd_group(u: int) -> int:
    """Query chunks per step of the scan-compiled forward: 2 when u is even,
    so each KV chunk fetched from the host serves two query chunks; else 1."""
    return 2 if u % 2 == 0 else 1


def kv_fetch_live(live, i0, j, g: int):
    """Is KV chunk j fetched for the query group i0 .. i0+g-1?  Yes when any
    of the group's pairs with it is live; ``live`` is ``pair_live`` or its
    traced twin."""
    out = live(i0, j)
    for d in range(1, g):
        out = out | live(i0 + d, j)
    return out


def fwd_kv_fetches(u: int, *, cq: int, window: int = 0, sparsity: float = 0.0) -> int:
    """KV chunk fetches one forward pass of one layer issues on the scan
    path: each group fetches the live chunks before its first query chunk
    once, and takes its own chunks from the device (u=16 dense: 56, where
    one query chunk per step fetches u(u-1)/2 = 120)."""
    g = fwd_group(u)
    live = functools.partial(pair_live, cq=cq, window=window, sparsity=sparsity)
    return sum(bool(kv_fetch_live(live, i0, j, g)) for i0 in range(0, u, g) for j in range(i0))


def overlapped_fetches(u: int, *, cq: int, head_groups: int, offload: bool, window: int = 0,
                       sparsity: float = 0.0) -> Tuple[int, int]:
    """(forward KV chunk fetches, Fig. 7 q chunk fetches) one layer issues on
    the overlapped path, each from the host while a chunk pair's kernels
    run: on a dense offloaded schedule whose kernels loop over
    ``head_groups`` > 1 (``head_groups()``), every fetch but each loop's
    first (u=16: 49 of the forward's 56 a pass, 120 of Fig. 7's 136).
    Otherwise (0, 0): windowed and sparse schedules fetch under
    ``lax.cond``, one head group leaves no loop to overlap, and without
    offload nothing is fetched from the host."""
    if window or sparsity > 0.0 or u == 1 or head_groups == 1 or not offload:
        return 0, 0
    loops = len(range(fwd_group(u), u, fwd_group(u)))  # groups with KV chunks before them
    return fwd_kv_fetches(u, cq=cq) - loops, u * (u - 1) // 2


# ---------------------------------------------------------------------------
# the chunk pipeline (forward + Fig.7 backward), cached per static config
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_fpdt(cfg: ModelConfig, par: ParallelContext, kind: str, window: int,
               u: int, offload: bool, seq_len: int, pos_offset: int):
    """Returns f(x, p) -> o  with x [b, S, d], o [b, S, hq*dh] (seq-sharded):
    the plain baseline at u = 1, the chunk pipeline at u > 1."""
    sparsity = cfg.attn_sparsity
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    impl = par.attn_impl
    chunk_fwd, chunk_bwd_dq, chunk_bwd_dkv = _chunk_kernels(par, kind, impl, hkv)
    # every pair the loops visit is live: no lax.cond, and each chunk pair's
    # kernels run as a loop over T head groups (module docstring)
    dense = not window and sparsity == 0.0
    T = head_groups(par, kind, hkv) if dense else 1
    bq, bk = cfg.block_q, cfg.block_k
    assert seq_len % u == 0, (seq_len, u)
    cq = seq_len // u
    # Offload *requested*: capability degradation (no pinned-host pool ->
    # identity + one logged warning) happens inside the placement policy.
    do_offload = offload and par.offload_to_host
    kv_spec = _host_spec_kv(par, kind, hkv, seq_len // u)
    q_spec = _host_spec_kv(par, kind, hq, seq_len // u)

    @jax.named_scope("fpdt.project")
    def project(p, xi, i):
        """(q, k, v) of hidden chunk i in head layout; i may be traced."""
        q, k, v = qkv_proj(cfg, p, xi)  # [b, cq, h, dh]
        pos = jnp.asarray(i, jnp.int32) * cq + pos_offset + jnp.arange(cq)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        q = _shard_q(par, kind, q)
        k = _shard_kv(par, kind, k)
        v = _shard_kv(par, kind, v)
        # head-major layout for the kernels
        return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    def unrope_back(g, i):
        """Backward of rope: rotate by -theta (orthogonal map); traced i ok."""
        pos = -(jnp.asarray(i, jnp.int32) * cq + pos_offset + jnp.arange(cq))
        return apply_rope(g, pos, cfg.rope_theta)

    live_py = functools.partial(pair_live, cq=cq, window=window, sparsity=sparsity)
    live_tr = functools.partial(pair_live_traced, cq=cq, window=window,
                                sparsity=sparsity)

    def to_host(t, spec=None):
        return par.to_host(t, *(spec or kv_spec)) if do_offload else t

    def to_dev(t, spec=None):
        return par.to_device(t, *(spec or kv_spec)) if do_offload else t

    # chunk-stacked [u, ...] stores: the leading chunk axis never shards
    kv_store_spec = (None,) + kv_spec
    q_store_spec = (None,) + q_spec

    def pair_kwargs(i, j):
        return dict(causal=True, window=window, q_offset=i * cq, k_offset=j * cq,
                    block_q=bq, block_k=bk, impl=impl)

    def finish(st, dtype, b):
        """A query chunk's final softmax state -> (o [b, hq, cq, dh] fp32,
        row LSE, o back in token layout [b, cq, hq*dh] and seq-sharded)."""
        st = SoftmaxState(*st)
        oi = finalize(st)  # [b, hq, cq, dh] fp32
        Li = lse(st)
        # back to token layout + seq sharding (inverse all-to-all)
        ot = oi.astype(dtype).transpose(0, 2, 1, 3)  # [b, cq, hq, dh]
        if par.mesh is not None and kind != "local":
            ot = par.constrain(ot, par.dp_axes, par.sp_axis, None, None)
        return oi, Li, ot.reshape(b, cq, hq * dh)

    @jax.named_scope("fpdt.unproject")
    def _unproject_body(p, i, xi, dq, dk, dv, b):
        """Per-chunk grad epilogue: a2a back, un-rope, un-project.
        Returns (dx_i, dW contributions).  i may be traced."""
        dq = dq.astype(xi.dtype).transpose(0, 2, 1, 3)  # [b, cq, h, dh]
        dk = dk.astype(xi.dtype).transpose(0, 2, 1, 3)
        dv = dv.astype(xi.dtype).transpose(0, 2, 1, 3)
        if par.mesh is not None and kind != "local":
            dq = par.constrain(dq, par.dp_axes, par.sp_axis, None, None)
            dk = par.constrain(dk, par.dp_axes, par.sp_axis, None, None)
            dv = par.constrain(dv, par.dp_axes, par.sp_axis, None, None)
        dq = unrope_back(dq, i)
        dk = unrope_back(dk, i)
        dqf = dq.reshape(b, cq, hq * dh)
        dkf = dk.reshape(b, cq, hkv * dh)
        dvf = dv.reshape(b, cq, hkv * dh)
        dx = dqf @ p["wq"].T + dkf @ p["wk"].T + dvf @ p["wv"].T
        # fp32 contributions: dW sums over u chunks, cast once at the end
        contrib = {
            n: jnp.einsum("bsd,bse->de", xi, g, preferred_element_type=jnp.float32)
            for n, g in (("wq", dqf), ("wk", dkf), ("wv", dvf))}
        if cfg.qkv_bias:
            contrib.update({n: jnp.sum(g.astype(jnp.float32), axis=(0, 1))
                            for n, g in (("bq", dqf), ("bk", dkf), ("bv", dvf))})
        return dx, contrib

    def _cast_like(dp, p):
        return {n: g.astype(p[n].dtype) for n, g in dp.items()}

    def head_loop(run, carry):
        """``run(t, carry)`` over the head groups, as a loop of its own: XLA's
        TPU scheduler overlaps a host fetch in flight with a loop, and not
        with Mosaic calls beside it."""
        return jax.lax.fori_loop(0, T, run, carry) if T > 1 else run(0, carry)

    def group(t):
        """Kernel keywords of head group t: its window of the q heads."""
        return {} if T == 1 else {"heads": (t * (hq // T), hq // T)}

    def put_group(x, t, v, *, at=(), add=False):
        """``x`` with head group ``t`` of ``x[at]`` set to ``v``, or ``v``
        added; heads follow the batch axis."""
        n, h = len(at), x.shape[len(at) + 1]
        start = tuple(at) + (0, t * (h // T)) + (0,) * (x.ndim - n - 2)
        v = v.reshape((1,) * n + v.shape)
        if add:
            v = jax.lax.dynamic_slice(x, start, v.shape) + v
        return jax.lax.dynamic_update_slice(x, v, start)

    # ================= u = 1: the plain Ulysses/CP baseline =================

    def fwd_plain(x, p):
        q, k, v = project(p, x, 0)
        o, L, ot = finish(chunk_fwd(q, k, v, None, **pair_kwargs(0, 0)), x.dtype, x.shape[0])
        if par.mesh is not None:
            ot = par.seq_sharded(ot)
        return ot, (x, p, q, k, v, o, L)

    def bwd_plain(res, do):
        x, p, q, k, v, o, L = res
        b = x.shape[0]
        do = _shard_q(par, kind, do.reshape(b, cq, hq, dh))
        do = do.transpose(0, 2, 1, 3).astype(jnp.float32)  # [b, hq, cq, dh]
        delta = jnp.sum(do * o, axis=-1)
        kwargs = pair_kwargs(0, 0)
        dk, dv = chunk_bwd_dkv(q, k, v, do, L, delta, **kwargs)
        dq = chunk_bwd_dq(q, k, v, do, L, delta, **kwargs)
        dx, dp = _unproject_body(p, 0, x, dq, dk, dv, b)
        if par.mesh is not None:
            dx = par.seq_sharded(dx)
        # wo is not part of this custom_vjp (out_proj applied by caller)
        return dx, _cast_like(dp, p)

    # ================= u > 1: the scan-compiled chunk pipeline ==============
    #
    # One lax.scan over query chunk groups; the KV store is a pair of
    # preallocated [u, b, h, cq, dh] carry buffers living in the offload pool
    # (placement-annotated after every append), appended with
    # dynamic_update_slice and read back chunk-by-chunk through the
    # double-buffered fori_loop.  HLO contains ONE copy of the chunk body.

    # Stores live in the offload pool and no op mixes memory spaces: a chunk
    # moves host-ward first and is written host-into-host; a read slices the
    # host store and then fetches the slice (XLA's host-offload pass turns
    # both into DMAs).
    @jax.named_scope("fpdt.store")
    def _store(store, chunk, i, spec):
        return jax.lax.dynamic_update_index_in_dim(store, to_host(chunk, spec),
                                                   i, axis=0)

    @jax.named_scope("fpdt.fetch")
    def _load(store, j, spec):
        return to_dev(jax.lax.dynamic_index_in_dim(store, j, axis=0,
                                                   keepdims=False), spec)

    # Query chunks run in groups of g (2 when u is even): each KV chunk
    # fetched from the host serves every query chunk of the group.
    g = fwd_group(u)

    def fwd_scan(x, p):
        b = x.shape[0]
        # [u, b, cq, d], the shape bwd_scan reads x in, so XLA makes x once
        xs = x.reshape(b, u, cq, -1).swapaxes(0, 1)
        proj_dtype = jnp.result_type(x.dtype, p["wq"].dtype)
        # stores start in the offload pool so the scan carry's memory
        # placement agrees between loop entry and the to_host'd body outputs
        with jax.named_scope("fpdt.store"):
            kv0 = jnp.zeros((u, b, hkv, cq, dh), proj_dtype)
            q0 = jnp.zeros((u, b, hq, cq, dh), proj_dtype)
            # tied to this layer's x: a pinned-host zero hoisted out of the
            # layer loop is copied to each layer by a host-executed call
            (kv0, q0), _ = jax.lax.optimization_barrier(((kv0, q0), x))
            kst0 = to_host(kv0, kv_store_spec)
            vst0 = to_host(kv0, kv_store_spec)
            qst0 = to_host(q0, q_store_spec)

        def body(carry, m):
            kst, vst, qst = carry
            i0 = m * g  # the group's first query chunk
            qkv = [project(p, jax.lax.dynamic_index_in_dim(xs, i0 + d, keepdims=False), i0 + d)
                   for d in range(g)]

            def fetch_kv(j):
                return _load(kst, j, kv_spec), _load(vst, j, kv_spec)

            def pair(j, kv, sts):
                kj, vj = kv

                def run(t, sts):
                    out = []
                    for d, st in enumerate(sts):
                        def attend(st, d=d):
                            return tuple(put_group(a, t, n) for a, n in zip(st, chunk_fwd(
                                qkv[d][0], kj, vj, st, **group(t), **pair_kwargs(i0 + d, j))))
                        # under a window or sparsity a fetched chunk may be
                        # live for one query of the group only
                        out.append(attend(st) if dense or g == 1 else jax.lax.cond(
                            live_tr(i0 + d, j), attend, lambda st: st, tuple(st)))
                    return tuple(out)
                return head_loop(run, sts)

            # KV chunks before the group: each fetched once for all g queries
            sts = fori_double_buffered(
                0, i0, fetch_kv, pair, tuple(tuple(zero_state_like(q)) for q, _, _ in qkv),
                live=None if dense else lambda j: kv_fetch_live(live_tr, i0, j, g))
            outs = []
            for d, st in enumerate(sts):
                qi = qkv[d][0]
                # the group's own chunks, projected on the device: no fetch;
                # liveness depends on i - j alone, so it is known here
                for e in range(d + 1):
                    if live_py(d, e):
                        st = chunk_fwd(qi, qkv[e][1], qkv[e][2], st,
                                       **pair_kwargs(i0 + d, i0 + e))
                outs.append(finish(st, x.dtype, b))
            for d, (qi, ki, vi) in enumerate(qkv):
                kst = _store(kst, ki, i0 + d, kv_spec)
                vst = _store(vst, vi, i0 + d, kv_spec)
                qst = _store(qst, qi, i0 + d, q_spec)
            return (kst, vst, qst), jax.tree.map(lambda *a: jnp.stack(a), *outs)

        (kst, vst, qst), ys = jax.lax.scan(
            body, (kst0, vst0, qst0), jnp.arange(u // g))
        ost, Lst, ots = (y.reshape((u,) + y.shape[2:]) for y in ys)  # [u, ...]
        o = ots.swapaxes(0, 1).reshape(b, seq_len, hq * dh)
        if par.mesh is not None:
            o = par.seq_sharded(o)
        return o, (x, p, kst, vst, qst, ost, Lst)

    def bwd_scan(res, do):
        x, p, kst, vst, qst, ost, Lst = res
        b = x.shape[0]
        # chunk-stacked head-layout do + delta: [u, b, hq, cq, dh] fp32
        dot = do.reshape(b, u, cq, hq, dh).swapaxes(0, 1)
        dot = _shard_q_stacked(par, kind, dot)
        dos = dot.transpose(0, 1, 3, 2, 4).astype(jnp.float32)
        deltas = jnp.sum(dos * ost, axis=-1)  # [u, b, hq, cq]

        def fetch_kv(j):
            return _load(kst, j, kv_spec), _load(vst, j, kv_spec)

        def fetch_q(i):
            return _load(qst, i, q_spec)

        # Fig. 7: outer scan over KV chunks j (dk_j/dv_j emitted as scan
        # outputs), inner double-buffered fori_loop over query chunks
        # i in [j, u) accumulating into the dq store carried across both.
        def outer(carry, j):
            dq_acc, kj, vj = carry
            knext, vnext = fetch_kv(jnp.minimum(j + 1, u - 1))  # Fig. 6 prefetch

            def pair(i, qi, st):
                doi, Li, di = (jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False)
                               for a in (dos, Lst, deltas))
                args, kwargs = (qi, kj, vj, doi, Li, di), pair_kwargs(i, j)

                def run(t, st):
                    dk, dv, dq_acc = st
                    dk_c, dv_c = chunk_bwd_dkv(*args, **group(t), **kwargs)
                    dq_c = chunk_bwd_dq(*args, **group(t), **kwargs)
                    return (put_group(dk, t, dk_c, add=True), put_group(dv, t, dv_c, add=True),
                            put_group(dq_acc, t, dq_c, at=(i,), add=True))
                return head_loop(run, st)

            z = jnp.zeros((b, hkv, cq, dh), jnp.float32)
            dk, dv, dq_acc = fori_double_buffered(
                j, u, fetch_q, pair, (z, z, dq_acc),
                live=None if dense else lambda i: live_tr(i, j))
            return (dq_acc, knext, vnext), (dk, dv)

        dq0 = jnp.zeros((u, b, hq, cq, dh), jnp.float32)
        k0, v0 = fetch_kv(0)
        (dqs, _, _), (dks, dvs) = jax.lax.scan(
            outer, (dq0, k0, v0), jnp.arange(u))

        # per-chunk grad epilogue as one more scan; dW accumulates in the carry
        xs = x.reshape(b, u, cq, -1).swapaxes(0, 1)

        def unproj(carry, inp):
            i, xi, dq, dk, dv = inp
            dx, contrib = _unproject_body(p, i, xi, dq, dk, dv, b)
            return jax.tree.map(jnp.add, carry, contrib), dx

        dp0 = {n: jnp.zeros(w.shape, jnp.float32) for n, w in p.items()}
        dp, dxs = jax.lax.scan(unproj, dp0, (jnp.arange(u), xs, dqs, dks, dvs))
        dx = dxs.swapaxes(0, 1).reshape(b, seq_len, -1)
        if par.mesh is not None:
            dx = par.seq_sharded(dx)
        return dx, _cast_like(dp, p)

    fwd, bwd = (fwd_plain, bwd_plain) if u == 1 else (fwd_scan, bwd_scan)

    @jax.custom_vjp
    def f(x, p):
        return fwd(x, p)[0]

    f.defvjp(fwd, bwd)
    # jit so fwd/bwd always trace: jax 0.9's eager slice of a host array on
    # CPU keeps a host aval under a device sharding, which device_put then
    # treats as already placed.
    return jax.jit(f)


def fpdt_attention(
    cfg: ModelConfig,
    par: Optional[ParallelContext],
    p: Params,
    x: jnp.ndarray,
    *,
    kind: str = "ulysses",
    window: int = 0,
    pos_offset: int = 0,
) -> jnp.ndarray:
    """Chunk-pipelined distributed attention over hidden states.

    x: [b, S, d] (seq-sharded).  Returns [b, S, hq*dh] (seq-sharded),
    ready for the output projection.  u = cfg.fpdt_chunks (1 = Ulysses/CP
    baseline, > 1 = the scan-compiled chunk pipeline); offload per
    cfg.fpdt_offload.
    """
    par = par if par is not None else ParallelContext(mesh=None)
    attn_p = {k_: p[k_] for k_ in ("wq", "wk", "wv", "bq", "bk", "bv") if k_ in p}
    f = _make_fpdt(cfg, par, kind, window, cfg.fpdt_chunks, cfg.fpdt_offload,
                   x.shape[1], pos_offset)
    return f(x, attn_p)
