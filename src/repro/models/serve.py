"""Serving path: cache init, prefill, and single-token decode.

Cache layouts (stacked over layer cycles C so decode scans one cycle body):
  attn        {"k","v": [C, b, S, hkv, dh], "kpos": [C, b, S] filled positions}
  local_attn  same with S = window (ring buffer; entry positions in "kpos")
  ssm         {"conv": [C, b, k-1, di], "ssm": [C, b, di, ds]}
  rglru       {"conv": [C, b, k-1, di], "h": [C, b, di]}

Decode positions are per-sequence: every entry point here accepts ``pos``
as a scalar or an int32 ``[b]`` vector, so a batch may hold sequences at
different depths (the continuous-batching engine in
``runtime/decode_loop.py`` relies on this).  ``kpos`` entries of ``-1``
mark unfilled/invalid cache slots; attention masks on ``kpos`` rather than
on slot index, which is what makes position-masked (padded) prefill exact.

Sharding: Ulysses archs shard cache *heads* over the model axis; CP archs
shard cache *sequence*; SSM/RG states shard channels.  With
``n_host_chunks > 0`` the attention KV cache lives in host memory (when
the backend's placement policy supports it) and decode streams it
chunk-by-chunk through the online-softmax merge via
``runtime.placement.fori_double_buffered`` — the same scan-carry Fig. 6
pipeline the training path uses, so decode program size is flat in the
chunk count and dead (unfilled) chunks skip both the host fetch and the
merge.  This is the FPDT pipeline applied to inference (the EXTRA
long_500k cell); see ``docs/serving.md``.

Paged layout (``init_paged_cache`` + ``table=...`` on the step entry
points): full-attention blocks swap the per-slot ``[b, S]`` rows for one
slot-SHARED page pool ``pk``/``pv`` ``[n_pages+1, page_size, hkv*dh]``
(lane-dense rows; + ``pkpos [n_pages+1, page_size]`` filled positions) indexed through a
per-slot page table ``[b, max_pages] int32`` owned by
``runtime/paged.py``: entry ``-1`` = unmapped (masked out of attention),
and the extra physical page (index ``n_pages``, the *trash* page) is
where a FREE slot's table row points so its dummy decode writes land
harmlessly.  Two slots may map the same physical page (radix prefix
reuse) — reads are free to share; the manager guarantees written pages
are exclusively owned (copy-on-write).  Recurrent states and local_attn
rings stay per-slot dense — they are O(1)/O(window) per slot, paging
buys nothing.

With ``n_host_chunks > 0`` the pool's ``pk``/``pv`` stay in the offload
pool for the whole program: every layer's pages are viewed as one row
array ``[C * (n_pages+1), page_size, hkv*dh]`` carried through the layer
scan, pages are fetched device-ward one page per slot (a host slice of
the page dim, then ``to_device``), and writes are whole-page
read-modify-writes (``to_host``, then a host-into-host update of the page
dim).  The host side only ever sees page-dim slices of a lane-dense
array, which is what the TPU host-offload DMAs accept.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.core.online_softmax import NEG_INF, SoftmaxState, finalize, merge, zero_state
from repro.core.parallel import ParallelContext
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import rglru as R
from repro.models.transformer import (
    attn_kind,
    head_matrix,
    layout_of,
    pattern_of,
)
from repro.runtime.placement import fori_double_buffered

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------


def _attn_cache(cfg, b, s, dtype):
    return {
        "k": jnp.zeros((b, s, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((b, s, cfg.num_kv_heads, cfg.head_dim), dtype),
        "kpos": jnp.full((b, s), -1, jnp.int32),
    }


def _block_cache(cfg: ModelConfig, kind: str, b: int, max_len: int, dtype):
    if kind == "attn":
        return _attn_cache(cfg, b, max_len, dtype)
    if kind == "local_attn":
        return _attn_cache(cfg, b, min(cfg.window, max_len), dtype)
    if kind == "ssm":
        return {
            "conv": jnp.zeros((b, cfg.d_conv - 1, cfg.d_inner), dtype),
            "ssm": jnp.zeros((b, cfg.d_inner, cfg.ssm_state), jnp.float32),
        }
    if kind == "rglru":
        return {
            "conv": jnp.zeros((b, cfg.d_conv - 1, cfg.d_inner), dtype),
            "h": jnp.zeros((b, cfg.d_inner), jnp.float32),
        }
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, b: int, max_len: int) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    pat, n_cycles, tail = layout_of(cfg)

    def stack(make):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_cycles, *x.shape)), make())

    cache = {
        f"pos{i}": stack(functools.partial(_block_cache, cfg, kind, b, max_len, dtype))
        for i, kind in enumerate(pat)
    }
    if tail:
        cache["tail"] = [_block_cache(cfg, kind, b, max_len, dtype) for kind in tail]
    return cache


def _paged_attn_cache(cfg: ModelConfig, n_pages: int, page_size: int, dtype):
    """Slot-shared page pool for one attention layer.  ``n_pages + 1``
    physical pages: the last one is the TRASH page — FREE slots' table rows
    point every logical page at it, so their dummy decode writes land
    somewhere harmless; it is never mapped by a live slot.  ``pkpos = -1``
    marks unfilled page entries, exactly like the dense ``kpos``.  A page
    row holds all kv heads side by side (``hkv * dh`` wide): lane-dense on
    the TPU, and the shape the host-offload DMAs take a page of."""
    width = cfg.num_kv_heads * cfg.head_dim
    return {
        "pk": jnp.zeros((n_pages + 1, page_size, width), dtype),
        "pv": jnp.zeros((n_pages + 1, page_size, width), dtype),
        "pkpos": jnp.full((n_pages + 1, page_size), -1, jnp.int32),
    }


def init_paged_cache(cfg: ModelConfig, b: int, n_pages: int, page_size: int) -> Params:
    """Paged twin of ``init_cache``: full-attention blocks share ONE page
    pool across all ``b`` slots (memory scales with pages actually used,
    not ``slots x worst-case length``; pages are mapped per slot through
    the ``runtime/paged.py`` page table, and a shared prompt prefix maps
    the same physical pages copy-free).  local_attn rings and recurrent
    ssm/rglru states keep their per-slot dense layouts."""
    dtype = jnp.dtype(cfg.param_dtype)
    pat, n_cycles, tail = layout_of(cfg)
    cap = n_pages * page_size  # pool token capacity bounds the ring window

    def make(kind):
        if kind == "attn":
            return _paged_attn_cache(cfg, n_pages, page_size, dtype)
        return _block_cache(cfg, kind, b, cap, dtype)

    def stack(kind):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_cycles, *x.shape)),
                            make(kind))

    cache = {f"pos{i}": stack(kind) for i, kind in enumerate(pat)}
    if tail:
        cache["tail"] = [make(kind) for kind in tail]
    return cache


def dense_kv_spec(par: ParallelContext, shape) -> tuple:
    """Spec components for one dense K/V leaf ``[b, s, h, dh]`` — THE
    shape-aware rule: batch over dp and heads over model when divisible
    (Ulysses-style), else fall back to sequence sharding (CP-style); a dim
    that divides nothing stays replicated."""
    b, s, h, _ = shape
    dp = par.dp_axes if b % par.dp == 0 and b >= par.dp else None
    if h % par.sp == 0 and h >= par.sp:
        return (dp, None, par.sp_axis, None)
    sp = par.sp_axis if s % par.sp == 0 and s >= par.sp else None
    return (dp, sp, None, None)


def paged_pool_spec(par: ParallelContext, page_size: int, hkv: int) -> tuple:
    """Spec components for one pool K/V leaf ``[n_pages+1, page_size,
    hkv*dh]`` — the dense rule transposed to the paged layout: kv heads
    (whole ``dh`` runs of the row) over the model axis when divisible, else
    the in-page sequence dim.  The physical-page dim is ``n_pages + 1``
    (trash page) and the page table maps pages to slots arbitrarily, so it
    is NEVER sharded — every device holds its head (or in-page) slice of
    every page, and the pool stays replicated over the data axis (it has
    no batch dim; slots split over data through the per-slot dense leaves
    instead)."""
    if hkv % par.sp == 0 and hkv >= par.sp:
        return (None, None, par.sp_axis)
    if page_size % par.sp == 0 and page_size >= par.sp:
        return (None, par.sp_axis, None)
    return (None, None, None)


def pool_host_spec(par: Optional[ParallelContext], ndim: int,
                   width: int) -> tuple:
    """Spec of a host-parked pool array (``ndim`` dims, rows ``width``
    wide): placement custom-calls refuse partial replication, so on a mesh
    the row dim is split over EVERY axis; off-mesh, no spec."""
    if par is None or par.mesh is None:
        return ()
    n = par.mesh.size
    if width % n:
        raise ValueError(
            f"a host-parked KV pool splits its {width}-wide page rows "
            f"(kv_heads * head_dim) over all {n} devices of the mesh, and "
            f"{width} % {n} != 0")
    return (None,) * (ndim - 1) + (tuple(par.mesh.axis_names),)


def _to_host(par, x):
    if par is None:
        return x
    return par.to_host(x, *pool_host_spec(par, x.ndim, x.shape[-1]))


def _to_dev(par, x):
    if par is None:
        return x
    return par.to_device(x, *pool_host_spec(par, x.ndim, x.shape[-1]))


def park_pool(par: Optional[ParallelContext], cache: Params) -> Params:
    """Place every pool ``pk``/``pv`` leaf in the offload pool (identity
    with ``par=None`` or offload off).  Used eagerly to park a pool and
    inside programs to give a host-resident argument its host memory
    space."""
    def park(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        return _to_host(par, leaf) if names[-1] in ("pk", "pv") else leaf

    return jax.tree_util.tree_map_with_path(park, cache)


def pool_rows(par: Optional[ParallelContext], leaf):
    """One pool K/V leaf (``[C, n, ps, W]`` stacked or ``[n, ps, W]``) as
    page rows ``[C*n, ps, W]`` in the offload pool: layer ``c``'s
    physical page ``p`` is row ``c*n + p``, so every host access slices
    the page dim only."""
    return _to_host(par, leaf.reshape(-1, *leaf.shape[-2:]))


def get_page(par: Optional[ParallelContext], leaf, pid):
    """Physical page ``pid`` of every layer of one pool K/V leaf, in device
    memory: ``[C, ps, W]`` (``[ps, W]`` for a tail leaf)."""
    n = leaf.shape[-3]
    rows = pool_rows(par, leaf)
    pages = [_to_dev(par, jax.lax.dynamic_slice_in_dim(rows, c * n + pid, 1, 0))[0]
             for c in range(rows.shape[0] // n)]
    return jnp.stack(pages) if leaf.ndim == 4 else pages[0]


def set_page(par: Optional[ParallelContext], leaf, pid, page):
    """Write ``page`` (``get_page``'s shape) into physical page ``pid`` of
    every layer of one pool K/V leaf; whole pages, host-into-host."""
    n = leaf.shape[-3]
    rows = pool_rows(par, leaf)
    pages = page if leaf.ndim == 4 else page[None]
    for c in range(pages.shape[0]):
        blk = _to_host(par, pages[c][None].astype(rows.dtype))
        rows = jax.lax.dynamic_update_slice_in_dim(rows, blk, c * n + pid, 0)
    return rows.reshape(leaf.shape)


def _fetch_pages(par, rows, base, pids):
    """Pages ``base + pids[i]`` of host-parked rows into device memory, one
    page-dim slice per slot: ``[b, ps, W]``."""
    return jnp.stack([
        _to_dev(par, jax.lax.dynamic_slice_in_dim(rows, base + pids[i], 1, 0))[0]
        for i in range(pids.shape[0])])


def _write_pages(par, kv_rows, base, table, tpos, vals, live, n_phys):
    """Write token rows into host-parked page rows through the page table.

    ``kv_rows`` = (pk rows, pv rows) ``[R, ps, W]``; ``tpos`` [b, t] the
    token positions (consecutive per slot), ``vals`` = (k, v) ``[b, t, W]``,
    ``live`` [b, t] which tokens land (the rest, and tokens on unmapped
    pages, are dropped, as the device path's ``mode="drop"`` scatter).
    Each touched page is fetched, updated on the device, and written back
    whole (a select, not an update-slice: the host side takes only
    page-dim slices); a page with nothing to write rewrites the trash
    page unchanged."""
    b, t = tpos.shape
    ps, max_pages = kv_rows[0].shape[1], table.shape[1]
    span = (ps + t - 2) // ps + 1  # pages a run of t positions can touch
    tj = jnp.minimum(tpos // ps, max_pages - 1)  # as _paged_write_ids clamps
    at_off = (tpos % ps)[..., None] == jnp.arange(ps)  # [b, t, ps]

    def body(i, kv_rows):
        s, j = i // span, tj[i // span, 0] + i % span
        pid = table[s, jnp.minimum(j, max_pages - 1)]
        hit = live[s] & (tj[s] == j) & (pid >= 0)  # [t]
        m = hit[:, None] & at_off[s]  # [t, ps]
        row = base + jnp.where(jnp.any(hit), pid, n_phys - 1)
        src = jnp.argmax(m, axis=0)  # the token landing at each offset
        out = []
        for rows, val in zip(kv_rows, vals):
            page = _to_dev(par, jax.lax.dynamic_slice_in_dim(rows, row, 1, 0))
            page = jnp.where(m.any(0)[None, :, None],
                             val[s][src][None].astype(rows.dtype), page)
            out.append(jax.lax.dynamic_update_slice_in_dim(
                rows, _to_host(par, page), row, 0))
        return tuple(out)

    return jax.lax.fori_loop(0, b * span, body, tuple(kv_rows))


def cache_shardings(cfg: ModelConfig, par: ParallelContext, cache, *,
                    host_pool: bool = False):
    """NamedShardings for a cache pytree (heads/seq/channels per DESIGN.md).

    Shape-aware: a dim is only sharded when divisible by its axis (kv heads
    smaller than the model axis fall back to sequence sharding; batch=1
    long-context decode leaves batch unsharded).  Covers BOTH layouts:
    dense per-slot rows (``init_cache``) and the slot-shared paged pool
    (``init_paged_cache`` — ``pk``/``pv`` follow ``paged_pool_spec``,
    ``pkpos`` co-shards its in-page dim with them).  ``host_pool``: the
    pool's ``pk``/``pv`` live in the offload pool (``pool_host_spec``)."""

    def dp_if(n):
        return par.dp_axes if n % par.dp == 0 and n >= par.dp else None

    def sp_if(n):
        return par.sp_axis if n % par.sp == 0 and n >= par.sp else None

    def spec_for(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        stacked = names[0] != "tail"
        lead = (None,) if stacked else ()
        off = 1 if stacked else 0
        shape = leaf.shape[off:]
        if names[-1] in ("pk", "pv"):  # [*, n_pages+1, ps, hkv*dh]
            if host_pool:
                return par.pol.ns(par.mesh, *pool_host_spec(par, leaf.ndim, shape[2]),
                                  on_host=True)
            return par.ns(*lead, *paged_pool_spec(par, shape[1], cfg.num_kv_heads))
        if "pkpos" in names:  # [*, n_pages+1, ps]
            sub = paged_pool_spec(par, shape[1], cfg.num_kv_heads)
            return par.ns(*lead, None, sub[1])
        if "kpos" in names:  # [*, b, s]
            return par.ns(*lead, dp_if(shape[0]), None)
        if names[-1] in ("k", "v"):  # [*, b, s, h, dh]
            return par.ns(*lead, *dense_kv_spec(par, shape))
        if "conv" in names:  # [*, b, k-1, di]
            return par.ns(*lead, dp_if(shape[0]), None, sp_if(shape[2]))
        if "ssm" in names:  # [*, b, di, ds]
            return par.ns(*lead, dp_if(shape[0]), sp_if(shape[1]), None)
        if names[-1] == "h":  # [*, b, di]
            return par.ns(*lead, dp_if(shape[0]), sp_if(shape[1]))
        return par.ns()

    return jax.tree_util.tree_map_with_path(spec_for, cache)


def pool_leaf_key(path) -> str:
    """Stable string key for one pool-cache leaf path — ``'pos0/pk'``,
    ``'tail/0/pkpos'``...  Used wherever a page payload crosses the
    pytree boundary into plain host dicts (the spill tier, ``page_rows``,
    ``runtime/paged.py::promote_page``): dict keys sort, so one key scheme
    means ONE pytree structure and therefore one compiled promote
    program.  Handles every path-entry flavour (``DictKey.key``,
    ``SequenceKey.idx``, attr ``name``)."""
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
    return "/".join(parts)


def page_rows(par: Optional[ParallelContext], cache, pid) -> Dict[str, Any]:
    """One physical page's payload keyed by ``pool_leaf_key`` — the
    demotion/persistence read path (traceable; ``par`` places a
    host-parked pool, ``None`` for a pool in device memory).  Only pool
    leaves (``pk``/``pv``/``pkpos``) appear; per-slot dense leaves carry
    no page state.  Stacked leaves keep their leading cycle dim, so a row
    is ``[C, ps, hkv*dh]`` / ``[C, ps]`` (or without ``C`` for tail)."""
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    rows = {}
    for path, leaf in leaves:
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        if names[-1] in ("pk", "pv"):
            rows[pool_leaf_key(path)] = get_page(par, leaf, pid)
        elif "pkpos" in names:
            rows[pool_leaf_key(path)] = leaf[:, pid] if names[0] != "tail" else leaf[pid]
    return rows


# ---------------------------------------------------------------------------
# decode attention (single new token against the cache)
# ---------------------------------------------------------------------------


def _decode_attention(cfg: ModelConfig, par: Optional[ParallelContext], p: Params,
                      x: jnp.ndarray, cache: Params, pos, *, window: int = 0,
                      n_host_chunks: int = 0):
    """x [b, 1, d]; pos scalar or [b]; returns (attn_out [b, 1, qd], new cache)."""
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))  # per-sequence
    q, k, v = L.qkv_proj(cfg, p, x)  # [b, 1, h, dh]
    q = L.apply_rope(q, pos[:, None], cfg.rope_theta)
    k = L.apply_rope(k, pos[:, None], cfg.rope_theta)
    S = cache["k"].shape[1]
    slot = jnp.where(window > 0, pos % S, jnp.minimum(pos, S - 1))  # [b]
    bi = jnp.arange(b)
    ck = cache["k"].at[bi, slot].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bi, slot].set(v[:, 0].astype(cache["v"].dtype))
    kpos = cache["kpos"].at[bi, slot].set(pos)
    if par is not None and par.mesh is not None:
        dspec = dense_kv_spec(par, ck.shape)
        ck = par.constrain(ck, *dspec)
        cv = par.constrain(cv, *dspec)

    g = cfg.num_heads // cfg.num_kv_heads
    qf = q[:, 0].astype(jnp.float32)  # [b, hq, dh]
    scale = cfg.head_dim ** -0.5

    def attend(kc, vc, kp):
        """Partial online-softmax state [b, h, 1, d] of q against this KV slab."""
        ke = jnp.repeat(kc.astype(jnp.float32), g, axis=2) if g > 1 else kc.astype(jnp.float32)
        ve = jnp.repeat(vc.astype(jnp.float32), g, axis=2) if g > 1 else vc.astype(jnp.float32)
        s = jnp.einsum("bhd,bshd->bhs", qf, ke) * scale
        ok = (kp >= 0) & (kp <= pos[:, None])
        if window:
            ok = ok & (kp > (pos - window)[:, None])
        s = jnp.where(ok[:, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        pr = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m[..., None]))
        l = pr.sum(-1)
        acc = jnp.einsum("bhs,bshd->bhd", pr, ve)
        return SoftmaxState(acc[:, :, None, :], m[:, :, None], l[:, :, None])

    if n_host_chunks and S % n_host_chunks == 0:
        _refuse_dense_offload(par)
        # FPDT-for-inference: stream host-resident KV chunk by chunk through
        # the scan-carry Fig. 6 pipeline — the chunk body is traced ONCE, so
        # decode program size is flat in n_host_chunks (the generator-based
        # double_buffered this replaced emitted one merge per chunk).
        cs = S // n_host_chunks
        # slab placement: seq over ALL axes (host<->device moves must not be
        # partially replicated), else unsharded
        slab_spec = None
        if par is not None and par.mesh is not None:
            all_axes = tuple(par.mesh.axis_names)
            if cs % par.mesh.size == 0:
                slab_spec = (None, all_axes, None, None)

        def fetch(c):
            kc = jax.lax.dynamic_slice_in_dim(ck, c * cs, cs, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(cv, c * cs, cs, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(kpos, c * cs, cs, axis=1)
            if par is not None:
                kc = par.to_device(kc, *(slab_spec or ()))
                vc = par.to_device(vc, *(slab_spec or ()))
            return kc, vc, kp

        # Liveness: full-attn slots fill [0, pos] in order (this path is
        # never taken for the windowed ring buffer), so a chunk whose first
        # slot lies beyond every sequence's position holds no valid entries
        # — skipping it skips the host fetch AND the merge, and is exact
        # because a fully-masked attend() yields merge's identity element.
        hi_pos = jnp.max(pos)
        state = fori_double_buffered(
            0, n_host_chunks, fetch,
            lambda c, buf, st: merge(st, attend(*buf)),
            zero_state((b, cfg.num_heads, 1, cfg.head_dim)),
            live=lambda c: (c * cs) <= hi_pos,
        )
        o = finalize(state)[:, :, 0]  # [b, h, d]
    else:
        o = finalize(attend(ck, cv, kpos))[:, :, 0]
    o = o.reshape(b, 1, cfg.q_dim).astype(x.dtype)
    out = o @ p["wo"]
    # NOTE: host residency of the updated cache comes from serve_step's
    # re-offload put through the placement policy — nothing explicit here.
    new_cache = {"k": ck, "v": cv, "kpos": kpos}
    return out, new_cache


def _refuse_dense_offload(par: Optional[ParallelContext]) -> None:
    """The dense cache is never parked in host memory (only the paged pool
    is): host-chunked attention over it streams device-resident chunks.
    That is a numerics twin of offload on CPU, where tests use it; on an
    accelerator it would be offload silently not done, so it is refused."""
    if par is not None and par.offload_active and par.pol.backend != "cpu":
        raise NotImplementedError(
            "n_host_chunks > 0 over the dense KV cache keeps the cache in "
            "device memory; host-parked KV serving is the paged engine's "
            "(PagedServeEngine(..., n_host_chunks=...))")


def _paged_write_ids(table: jnp.ndarray, pos: jnp.ndarray, page_size: int,
                     n_phys: int):
    """(physical page, in-page offset) for writing at ``pos`` through the
    page table.  Negative (unmapped) entries are redirected out of bounds
    so a ``mode="drop"`` scatter skips them — live positions are always
    mapped (the manager allocates a slot's full reserve at admit)."""
    max_pages = table.shape[1]
    j = jnp.minimum(pos // page_size, max_pages - 1)
    pid = jnp.take_along_axis(table, j.reshape(table.shape[0], -1), axis=1)
    pid = pid.reshape(pos.shape)
    pid = jnp.where(pid < 0, n_phys, pid)  # never wrap: OOB -> dropped
    return pid, pos % page_size


def _paged_history(cfg: ModelConfig, par, pk, pv, kpos, table, base, attend,
                   init: SoftmaxState, hi_pos):
    """Online-softmax state of ``attend(k, v, kpos, mapped)`` over every
    mapped page of each slot (``[b, S, hkv, dh]`` K/V runs; ``mapped``
    masks entries whose logical page is unmapped, ``-1`` in the table).

    ``base is None``: ``pk``/``pv`` are this layer's pool in device memory,
    and the whole mapped range is gathered at once (bit-comparable to dense
    attention).  Otherwise they are every layer's page rows parked in the
    offload pool (this layer's pages start at row ``base``), streamed one
    logical page per iteration through ``fori_double_buffered`` — the fetch
    of page j+1 is issued before page j's merge (Fig. 6), program size is
    flat in ``n_pages`` and ``max_pages``, and pages past every slot's
    highest position ``hi_pos`` are skipped."""
    b, max_pages = table.shape
    ps = kpos.shape[1]
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    if base is None:
        safe = jnp.clip(table, 0, None)  # [b, max_pages]
        kall = jnp.take(pk, safe, axis=0).reshape(b, max_pages * ps, hkv, dh)
        vall = jnp.take(pv, safe, axis=0).reshape(b, max_pages * ps, hkv, dh)
        kpall = jnp.take(kpos, safe, axis=0).reshape(b, max_pages * ps)
        return attend(kall, vall, kpall, jnp.repeat(table >= 0, ps, axis=1))

    def fetch(j):
        pid = jnp.clip(table[:, j], 0, None)
        kc = _fetch_pages(par, pk, base, pid).reshape(b, ps, hkv, dh)
        vc = _fetch_pages(par, pv, base, pid).reshape(b, ps, hkv, dh)
        kp = jnp.take(kpos, pid, axis=0)
        return kc, vc, kp, jnp.broadcast_to((table[:, j] >= 0)[:, None], kp.shape)

    return fori_double_buffered(
        0, max_pages, fetch, lambda j, buf, st: merge(st, attend(*buf)), init,
        live=lambda j: (j * ps) <= hi_pos)


def _decode_attention_paged(cfg: ModelConfig, par: Optional[ParallelContext],
                            p: Params, x: jnp.ndarray, cache: Params, pos,
                            table: jnp.ndarray, *, pool_base=None):
    """Paged twin of ``_decode_attention``: K/V are gathered through the
    per-slot page table instead of sliced from a dense ``[b, S]`` row.

    x [b, 1, d]; pos scalar or [b]; table [b, max_pages] int32 (physical
    page of each logical page; -1 = unmapped -> masked; FREE rows point at
    the trash page).  ``pool_base`` selects the pool's placement (see
    ``_paged_history``): ``None`` for this layer's pool in device memory,
    else the first page row of this layer in the host-parked rows that
    ``cache["pk"]``/``["pv"]`` then hold (the new token is written there by
    a page read-modify-write before the pages stream back).
    Returns (attn_out [b, 1, qd], new pool leaves)."""
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q, k, v = L.qkv_proj(cfg, p, x)  # [b, 1, h, dh]
    q = L.apply_rope(q, pos[:, None], cfg.rope_theta)
    k = L.apply_rope(k, pos[:, None], cfg.rope_theta)
    n_phys, ps = cache["pkpos"].shape
    k, v = k.reshape(b, 1, -1), v.reshape(b, 1, -1)  # [b, 1, hkv*dh] page rows
    pid_w, off = _paged_write_ids(table, pos, ps, n_phys)  # [b], [b]
    kpos = cache["pkpos"].at[pid_w, off].set(pos, mode="drop")
    if pool_base is None:
        ck = cache["pk"].at[pid_w, off].set(k[:, 0].astype(cache["pk"].dtype), mode="drop")
        cv = cache["pv"].at[pid_w, off].set(v[:, 0].astype(cache["pv"].dtype), mode="drop")
    else:
        ck, cv = _write_pages(par, (cache["pk"], cache["pv"]), pool_base, table,
                              pos[:, None], (k, v), jnp.ones((b, 1), bool), n_phys)
    if par is not None and par.mesh is not None:
        pspec = paged_pool_spec(par, ps, cfg.num_kv_heads)
        if pool_base is None:
            ck = par.constrain(ck, *pspec)
            cv = par.constrain(cv, *pspec)
        kpos = par.constrain(kpos, None, pspec[1])

    g = cfg.num_heads // cfg.num_kv_heads
    qf = q[:, 0].astype(jnp.float32)  # [b, hq, dh]
    scale = cfg.head_dim ** -0.5

    def attend(kc, vc, kp, okp):
        """Partial state of q against a gathered page run; ``okp`` masks
        entries whose logical page is unmapped in this slot's table."""
        ke = jnp.repeat(kc.astype(jnp.float32), g, axis=2) if g > 1 else kc.astype(jnp.float32)
        ve = jnp.repeat(vc.astype(jnp.float32), g, axis=2) if g > 1 else vc.astype(jnp.float32)
        s = jnp.einsum("bhd,bshd->bhs", qf, ke) * scale
        ok = okp & (kp >= 0) & (kp <= pos[:, None])
        s = jnp.where(ok[:, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        pr = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m[..., None]))
        l = pr.sum(-1)
        acc = jnp.einsum("bhs,bshd->bhd", pr, ve)
        return SoftmaxState(acc[:, :, None, :], m[:, :, None], l[:, :, None])

    state = _paged_history(cfg, par, ck, cv, kpos, table, pool_base, attend,
                           zero_state((b, cfg.num_heads, 1, cfg.head_dim)),
                           jnp.max(pos))
    o = finalize(state)[:, :, 0]  # [b, h, d]
    o = o.reshape(b, 1, cfg.q_dim).astype(x.dtype)
    out = o @ p["wo"]
    return out, {"pk": ck, "pv": cv, "pkpos": kpos}


def _decode_block(cfg, par, kind, p, h, cache, pos, n_host_chunks=0, table=None,
                  pool_base=None):
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else 0
        hn = L.apply_norm(cfg, p["norm1"], h)
        if table is not None and "pk" in cache:  # paged pool (attn only)
            o, cache = _decode_attention_paged(cfg, par, p["attn"], hn, cache,
                                               pos, table, pool_base=pool_base)
        else:
            o, cache = _decode_attention(cfg, par, p["attn"], hn, cache, pos,
                                         window=window,
                                         n_host_chunks=0 if kind == "local_attn" else n_host_chunks)
        h = h + o
        hn2 = L.apply_norm(cfg, p["norm2"], h)
        if cfg.num_experts:
            from repro.models import moe as MOE

            y, _ = MOE.moe_ffn(cfg, p["moe"], hn2)
        else:
            y = L.mlp_block(cfg, p["mlp"], hn2)
        return h + y, cache
    if kind == "ssm":
        hn = L.apply_norm(cfg, p["norm"], h)
        y, st = M.mamba_decode_step(cfg, p["mixer"], hn, cache)
        return h + y, st
    if kind == "rglru":
        hn = L.apply_norm(cfg, p["norm1"], h)
        y, st = R.rglru_decode_step(cfg, p["mixer"], hn, cache)
        h = h + y
        hn2 = L.apply_norm(cfg, p["norm2"], h)
        return h + L.mlp_block(cfg, p["mlp"], hn2), st
    raise ValueError(kind)


def _run_blocks(cfg: ModelConfig, par, params: Params, cache: Params, h,
                block, *, host_pool: bool):
    """Run every block over ``h``: the layer cycles in one ``lax.scan``,
    then the tail.  ``block(kind, p, h, cache, pool_base)`` returns
    ``(h, new block cache)``.

    With ``host_pool`` the paged pool's ``pk``/``pv`` are not sliced per
    cycle (slicing a layer out of a host array would copy it): each leaf
    rides the scan carry whole, as ``pool_rows`` in the offload pool, and
    cycle ``c`` addresses its pages from row ``c * (n_pages + 1)``."""
    pat, n_cycles, tail = layout_of(cfg)
    cyc = {k: cache[k] for k in cache if k != "tail"}
    rows = {}
    if host_pool:
        for k, sub in cyc.items():
            if "pk" in sub:
                rows[k] = {n: pool_rows(par, sub[n]) for n in ("pk", "pv")}
                cyc[k] = {"pkpos": sub["pkpos"]}

    def cycle_body(carry, scans):
        h, rows = carry
        cyc_p, cyc_cache, c = scans
        new_caches, new_rows = {}, {}
        for i, kind in enumerate(pat):
            key, base = f"pos{i}", None
            lc = cyc_cache[key]
            if key in rows:
                base = c * lc["pkpos"].shape[0]
                lc = {**lc, **rows[key]}
            h, nc = block(kind, cyc_p[key], h, lc, base)
            if key in rows:
                new_rows[key] = {n: nc.pop(n) for n in ("pk", "pv")}
            new_caches[key] = nc
        return (h, new_rows), new_caches

    (h, rows), new_cache = jax.lax.scan(
        cycle_body, (h, rows), (params["cycles"], cyc, jnp.arange(n_cycles)))
    new_cache = dict(new_cache)
    for k, r in rows.items():
        new_cache[k] = {**new_cache[k],
                        **{n: r[n].reshape(cache[k][n].shape) for n in r}}
    if tail:
        new_tail = []
        for i, kind in enumerate(tail):
            lc, base = cache["tail"][i], None
            if host_pool and "pk" in lc:
                lc = {**lc, **{n: pool_rows(par, lc[n]) for n in ("pk", "pv")}}
                base = 0
            h, nc = block(kind, params["tail"][i], h, lc, base)
            new_tail.append(nc)
        new_cache["tail"] = new_tail
    return h, new_cache


def decode_step(cfg: ModelConfig, par: Optional[ParallelContext], params: Params,
                cache: Params, inp: Dict[str, jnp.ndarray], pos,
                n_host_chunks: int = 0, table: Optional[jnp.ndarray] = None):
    """One decode step: advance every sequence in the batch by one token.

    Contract:
      inp    — {"tokens": [b, 1] int32} or {"frame_embeds": [b, 1, d]}.
      pos    — scalar or int32 [b]: the position each sequence's incoming
               token occupies.  The token is written into its cache slot
               (``kpos[slot] = pos``) and attends to entries with
               ``0 <= kpos <= pos``, so batch rows may sit at different
               depths.
      cache  — pytree from ``init_cache``/``prefill_step``; the returned
               cache is the same pytree with exactly the ``pos`` slots of
               every layer updated (shape- and dtype-stable, so it can ride
               a ``lax.scan`` carry — see ``runtime/decode_loop.py``).
      n_host_chunks — stream each attention layer's KV in this many chunks
               through ``fori_double_buffered`` (0 = on-device attention).
      table  — optional [b, max_pages] int32 page table: attention blocks
               read/write the slot-shared paged pool through it
               (``init_paged_cache`` layout; see ``runtime/paged.py``).

    Returns (logits [b, vocab] fp32, new cache)."""
    if cfg.frontend == "audio_frames":
        h = inp["frame_embeds"]
        # sinusoidal positional embedding at the (traced) decode position(s)
        b, d = h.shape[0], cfg.d_model
        posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
        ang = posb.astype(jnp.float32)[:, None] / jnp.power(10000.0, dim / d)
        pe = jnp.zeros((b, d), jnp.float32).at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
        h = h + pe.astype(h.dtype)[:, None]
    else:
        h = params["embed"][inp["tokens"]].astype(jnp.dtype(cfg.param_dtype))
    def block(kind, p, h, c, base):
        return _decode_block(cfg, par, kind, p, h, c, pos, n_host_chunks, table,
                             pool_base=base)

    h, new_cache = _run_blocks(cfg, par, params, cache, h, block,
                               host_pool=bool(n_host_chunks) and table is not None)
    h = L.apply_norm(cfg, params["final_norm"], h)
    logits = (h[:, 0] @ head_matrix(cfg, params)).astype(jnp.float32)
    return logits, new_cache


# ---------------------------------------------------------------------------
# fused mixed step: one prefill chunk OR one decode token per batch row
# ---------------------------------------------------------------------------


def _chunk_attention(cfg: ModelConfig, par: Optional[ParallelContext], p: Params,
                     x: jnp.ndarray, cache: Params, qpos: jnp.ndarray,
                     live: jnp.ndarray, *, window: int = 0,
                     n_host_chunks: int = 0):
    """Chunk-window attention against the cache at a traced offset.

    x [b, cp, d]; qpos [b, cp] the position of each window token; live [b]
    how many leading window tokens are real (0 = row is a complete no-op).
    Attention = online-softmax merge of (a) the PRE-write cache, masked on
    ``kpos`` (optionally host-streamed), and (b) the window's own keys
    under an intra-window causal mask — then the ``live`` keys are written
    into the cache (``mode="drop"`` scatter: dead positions never land, so
    a row with live=0 leaves its cache untouched).  ``live = 1`` is
    exactly one decode step; ``live = cp`` is one dense prefill chunk.
    Returns (attn out [b, cp, qd], new cache)."""
    b, cp, _ = x.shape
    q, k, v = L.qkv_proj(cfg, p, x)  # [b, cp, h, dh]
    q = L.apply_rope(q, qpos, cfg.rope_theta)
    k = L.apply_rope(k, qpos, cfg.rope_theta)
    S = cache["k"].shape[1]
    if window:
        window = min(window, S)  # ring capacity bounds the visible window
    g = cfg.num_heads // cfg.num_kv_heads
    qt = q.astype(jnp.float32).transpose(0, 2, 1, 3)  # [b, hq, cp, dh]
    scale = cfg.head_dim ** -0.5
    key_live = jnp.arange(cp)[None, :] < live[:, None]  # [b, cp]

    def expand(t):
        t = t.astype(jnp.float32)
        return jnp.repeat(t, g, axis=2) if g > 1 else t

    def attend(kc, vc, kp):
        """Partial state [b, h, cp, dh] of the window queries vs a KV slab."""
        ke, ve = expand(kc), expand(vc)
        s_ = jnp.einsum("bhqd,bshd->bhqs", qt, ke) * scale
        ok = (kp[:, None, :] >= 0) & (kp[:, None, :] <= qpos[:, :, None])
        if window:
            ok = ok & (kp[:, None, :] > (qpos[:, :, None] - window))
        s_ = jnp.where(ok[:, None], s_, NEG_INF)
        m = jnp.max(s_, axis=-1)
        pr = jnp.where(s_ <= NEG_INF / 2, 0.0, jnp.exp(s_ - m[..., None]))
        l = pr.sum(-1)
        acc = jnp.einsum("bhqs,bshd->bhqd", pr, ve)
        return SoftmaxState(acc, m, l)

    def attend_intra():
        """The window attending to its own (live, causal) keys — these are
        not in the cache yet, which is what makes the pre-write cache pass
        exact: no entry is double-counted, and ring-buffer eviction cannot
        clobber history the earlier window tokens still need."""
        ke, ve = expand(k), expand(v)
        s_ = jnp.einsum("bhqd,bkhd->bhqk", qt, ke) * scale
        ok = key_live[:, None, :] & (qpos[:, None, :] <= qpos[:, :, None])
        if window:
            ok = ok & (qpos[:, None, :] > (qpos[:, :, None] - window))
        s_ = jnp.where(ok[:, None], s_, NEG_INF)
        m = jnp.max(s_, axis=-1)
        pr = jnp.where(s_ <= NEG_INF / 2, 0.0, jnp.exp(s_ - m[..., None]))
        l = pr.sum(-1)
        acc = jnp.einsum("bhqk,bkhd->bhqd", pr, ve)
        return SoftmaxState(acc, m, l)

    if n_host_chunks and S % n_host_chunks == 0 and not window:
        _refuse_dense_offload(par)
        # FPDT-for-inference, mixed-step flavor: stream the pre-write cache
        # slab by slab (chunk body traced once — program size flat in the
        # slab count), merge with the intra-window part at the end.
        cs = S // n_host_chunks
        slab_spec = None
        if par is not None and par.mesh is not None:
            all_axes = tuple(par.mesh.axis_names)
            if cs % par.mesh.size == 0:
                slab_spec = (None, all_axes, None, None)

        def fetch(c):
            kc = jax.lax.dynamic_slice_in_dim(cache["k"], c * cs, cs, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(cache["v"], c * cs, cs, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(cache["kpos"], c * cs, cs, axis=1)
            if par is not None:
                kc = par.to_device(kc, *(slab_spec or ()))
                vc = par.to_device(vc, *(slab_spec or ()))
            return kc, vc, kp

        # full-attn slots fill [0, pos] in order, so a slab starting past
        # every row's highest live position holds no valid entries
        hi_pos = jnp.max(jnp.where(key_live, qpos, -1))
        hist = fori_double_buffered(
            0, n_host_chunks, fetch,
            lambda c, buf, st: merge(st, attend(*buf)),
            zero_state((b, cfg.num_heads, cp, cfg.head_dim)),
            live=lambda c: (c * cs) <= hi_pos,
        )
    else:
        hist = attend(cache["k"], cache["v"], cache["kpos"])

    o = finalize(merge(hist, attend_intra()))  # [b, h, cp, dh]
    o = o.transpose(0, 2, 1, 3).reshape(b, cp, cfg.q_dim).astype(x.dtype)
    out = o @ p["wo"]

    # write the live window into the cache (after attention).  Ring buffers
    # additionally drop all but the last S (ring capacity) live tokens — the
    # only survivors of intra-window eviction, and mutually collision-free.
    wmask = key_live
    if window:
        wmask = wmask & (jnp.arange(cp)[None, :] >= (live[:, None] - S))
        slot = qpos % S
    else:
        slot = qpos
    slot = jnp.where(wmask, slot, S)  # dead/evicted -> out of bounds, dropped
    bi = jnp.arange(b)[:, None]
    ck = cache["k"].at[bi, slot].set(k.astype(cache["k"].dtype), mode="drop")
    cv = cache["v"].at[bi, slot].set(v.astype(cache["v"].dtype), mode="drop")
    kpos = cache["kpos"].at[bi, slot].set(qpos, mode="drop")
    if par is not None and par.mesh is not None:
        dspec = dense_kv_spec(par, ck.shape)
        ck = par.constrain(ck, *dspec)
        cv = par.constrain(cv, *dspec)
    return out, {"k": ck, "v": cv, "kpos": kpos}


def _chunk_attention_paged(cfg: ModelConfig, par: Optional[ParallelContext],
                           p: Params, x: jnp.ndarray, cache: Params,
                           qpos: jnp.ndarray, live: jnp.ndarray,
                           table: jnp.ndarray, *, pool_base=None):
    """Paged twin of ``_chunk_attention``: the history pass gathers the
    PRE-write pool through the page table (``_paged_history``: page by
    page from the host-parked rows when ``pool_base`` is given, one gather
    otherwise), the intra-window pass is identical to dense, and the live
    window tokens are written back through the table (dead positions
    dropped).  Shared
    (radix) pages are only ever read — the page manager guarantees every
    written page is exclusively owned (COW).  Returns
    (attn out [b, cp, qd], new pool leaves)."""
    b, cp, _ = x.shape
    q, k, v = L.qkv_proj(cfg, p, x)  # [b, cp, h, dh]
    q = L.apply_rope(q, qpos, cfg.rope_theta)
    k = L.apply_rope(k, qpos, cfg.rope_theta)
    n_phys, ps = cache["pkpos"].shape
    g = cfg.num_heads // cfg.num_kv_heads
    qt = q.astype(jnp.float32).transpose(0, 2, 1, 3)  # [b, hq, cp, dh]
    scale = cfg.head_dim ** -0.5
    key_live = jnp.arange(cp)[None, :] < live[:, None]  # [b, cp]

    def expand(t):
        t = t.astype(jnp.float32)
        return jnp.repeat(t, g, axis=2) if g > 1 else t

    def attend(kc, vc, kp, okp):
        """Window queries vs a gathered page run; ``okp`` masks entries of
        unmapped logical pages."""
        ke, ve = expand(kc), expand(vc)
        s_ = jnp.einsum("bhqd,bshd->bhqs", qt, ke) * scale
        ok = okp[:, None, :] & (kp[:, None, :] >= 0) & (kp[:, None, :] <= qpos[:, :, None])
        s_ = jnp.where(ok[:, None], s_, NEG_INF)
        m = jnp.max(s_, axis=-1)
        pr = jnp.where(s_ <= NEG_INF / 2, 0.0, jnp.exp(s_ - m[..., None]))
        l = pr.sum(-1)
        acc = jnp.einsum("bhqs,bshd->bhqd", pr, ve)
        return SoftmaxState(acc, m, l)

    def attend_intra():
        """The window vs its own (live, causal) keys — not yet in the pool,
        so the pre-write history pass double-counts nothing."""
        ke, ve = expand(k), expand(v)
        s_ = jnp.einsum("bhqd,bkhd->bhqk", qt, ke) * scale
        ok = key_live[:, None, :] & (qpos[:, None, :] <= qpos[:, :, None])
        s_ = jnp.where(ok[:, None], s_, NEG_INF)
        m = jnp.max(s_, axis=-1)
        pr = jnp.where(s_ <= NEG_INF / 2, 0.0, jnp.exp(s_ - m[..., None]))
        l = pr.sum(-1)
        acc = jnp.einsum("bhqk,bkhd->bhqd", pr, ve)
        return SoftmaxState(acc, m, l)

    hist = _paged_history(cfg, par, cache["pk"], cache["pv"], cache["pkpos"],
                          table, pool_base, attend,
                          zero_state((b, cfg.num_heads, cp, cfg.head_dim)),
                          jnp.max(jnp.where(key_live, qpos, -1)))

    o = finalize(merge(hist, attend_intra()))  # [b, h, cp, dh]
    o = o.transpose(0, 2, 1, 3).reshape(b, cp, cfg.q_dim).astype(x.dtype)
    out = o @ p["wo"]

    # write the live window through the table (after attention)
    pid_w, off = _paged_write_ids(table, qpos, ps, n_phys)  # [b, cp] each
    pid_w = jnp.where(key_live, pid_w, n_phys)  # dead -> OOB, dropped
    kpos = cache["pkpos"].at[pid_w, off].set(qpos, mode="drop")
    k, v = k.reshape(b, cp, -1), v.reshape(b, cp, -1)  # [b, cp, hkv*dh] rows
    if pool_base is None:
        ck = cache["pk"].at[pid_w, off].set(k.astype(cache["pk"].dtype), mode="drop")
        cv = cache["pv"].at[pid_w, off].set(v.astype(cache["pv"].dtype), mode="drop")
    else:
        ck, cv = _write_pages(par, (cache["pk"], cache["pv"]), pool_base, table,
                              qpos, (k, v), key_live, n_phys)
    if par is not None and par.mesh is not None:
        pspec = paged_pool_spec(par, ps, cfg.num_kv_heads)
        if pool_base is None:
            ck = par.constrain(ck, *pspec)
            cv = par.constrain(cv, *pspec)
        kpos = par.constrain(kpos, None, pspec[1])
    return out, {"pk": ck, "pv": cv, "pkpos": kpos}


def _chunk_block(cfg, par, kind, p, h, cache, qpos, live, n_host_chunks=0,
                 table=None, pool_base=None):
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else 0
        hn = L.apply_norm(cfg, p["norm1"], h)
        if table is not None and "pk" in cache:  # paged pool (attn only)
            o, cache = _chunk_attention_paged(cfg, par, p["attn"], hn, cache,
                                              qpos, live, table,
                                              pool_base=pool_base)
        else:
            o, cache = _chunk_attention(cfg, par, p["attn"], hn, cache, qpos, live,
                                        window=window,
                                        n_host_chunks=0 if kind == "local_attn" else n_host_chunks)
        h = h + o
        hn2 = L.apply_norm(cfg, p["norm2"], h)
        if cfg.num_experts:
            from repro.models import moe as MOE

            y, _ = MOE.moe_ffn(cfg, p["moe"], hn2)
        else:
            y = L.mlp_block(cfg, p["mlp"], hn2)
        return h + y, cache
    if kind == "ssm":
        hn = L.apply_norm(cfg, p["norm"], h)
        y, st = M.mamba_chunk_step(cfg, p["mixer"], hn, cache, live)
        return h + y, st
    if kind == "rglru":
        hn = L.apply_norm(cfg, p["norm1"], h)
        y, st = R.rglru_chunk_step(cfg, p["mixer"], hn, cache, live)
        h = h + y
        hn2 = L.apply_norm(cfg, p["norm2"], h)
        return h + L.mlp_block(cfg, p["mlp"], hn2), st
    raise ValueError(kind)


def chunk_step(cfg: ModelConfig, par: Optional[ParallelContext], params: Params,
               cache: Params, toks: jnp.ndarray, offset, live,
               n_host_chunks: int = 0, table: Optional[jnp.ndarray] = None):
    """One fused mixed step: every batch row processes a ``cp``-token window.

    Contract:
      toks   — [b, cp] int32 window tokens.  A row consuming a prefill
               chunk passes the chunk (``live`` real tokens, rest padding);
               a row decoding passes its next token broadcast (``live=1``);
               an idle row passes anything (``live=0`` — complete no-op:
               cache, recurrent state and ring buffers are untouched).
      offset — scalar or int32 [b]: the position of each row's first window
               token (a prefilling row's chunk offset; a decoding row's
               ``pos``).
      live   — scalar or int32 [b] in [0, cp]: real tokens per row.
      cache  — pytree from ``init_cache``; updated in place at the live
               positions only (shape/dtype-stable — rides the mixed-step
               ``lax.scan`` carry in ``runtime/decode_loop.py``).
      table  — optional [b, max_pages] int32 page table for the paged pool
               (``init_paged_cache`` layout; see ``runtime/paged.py``).

    Recurrent blocks (ssm / rglru / local_attn ring) are handled by the
    *state-at-length gather*: pad positions are identity transitions and
    the conv carry is gathered at the true length
    (``mamba.mamba_chunk_step`` / ``rglru.rglru_chunk_step``), so
    variable-length chunked prefill is exact for state-integrating layouts
    — the capability that admits them into continuous batching.

    Returns (logits [b, vocab] fp32 at each row's LAST live token, cache).
    """
    if cfg.frontend == "audio_frames":
        raise ValueError("chunk_step feeds token ids; the audio_frames "
                         "frontend consumes frame embeddings")
    b, cp = toks.shape
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    live = jnp.broadcast_to(jnp.asarray(live, jnp.int32), (b,))
    qpos = offset[:, None] + jnp.arange(cp)[None, :]  # [b, cp]
    h = params["embed"][toks].astype(jnp.dtype(cfg.param_dtype))
    def block(kind, p, h, c, base):
        return _chunk_block(cfg, par, kind, p, h, c, qpos, live, n_host_chunks,
                            table, pool_base=base)

    h, new_cache = _run_blocks(cfg, par, params, cache, h, block,
                               host_pool=bool(n_host_chunks) and table is not None)
    h = L.apply_norm(cfg, params["final_norm"], h)
    li = jnp.clip(live - 1, 0, cp - 1)
    last = h[jnp.arange(b), li]
    logits = (last @ head_matrix(cfg, params)).astype(jnp.float32)
    return logits, new_cache


# ---------------------------------------------------------------------------
# prefill: forward + cache population
# ---------------------------------------------------------------------------


def prefill_step(cfg: ModelConfig, par: Optional[ParallelContext], params: Params,
                 batch: Dict[str, jnp.ndarray], max_len: int,
                 lengths: Optional[jnp.ndarray] = None):
    """Forward over the prompt batch, returning (logits, filled cache).

    Contract:
      batch   — prompt batch ({"tokens": [b, s]} or frontend equivalents);
                every row runs the full s-length forward.
      max_len — cache capacity (prompt + generation budget); the returned
                cache is ready for ``decode_step`` at ``pos = s`` (or
                ``pos = lengths`` per row).
      lengths — optional int32 [b] of true prompt lengths for
                *position-masked* prefill of RIGHT-padded variable-length
                prompts: cache entries at positions >= ``lengths[i]`` are
                marked invalid (``kpos = -1``) and row i's logits are taken
                at its last real token (position ``lengths[i] - 1``) rather
                than at s-1.  Right padding + causal attention guarantee
                real tokens never attend to pads, so this is exact for
                global-attention blocks.  Recurrent states (ssm/rglru) and
                the local_attn ring buffer integrate pad tokens into their
                carry, so archs containing those block kinds must prefill
                at exact length (raises ValueError).

    Returns (logits [b, vocab] fp32 at each row's last real token, cache).
    """
    from repro.models import transformer as T

    h = T.embed_input(cfg, params, batch)
    h = h.astype(jnp.dtype(cfg.param_dtype))
    b, s, _ = h.shape
    pat, n_cycles, tail = layout_of(cfg)
    if lengths is not None:
        bad = {k for k in (*pat, *tail) if k != "attn"}
        if bad:
            raise ValueError(
                f"position-masked prefill (lengths=...) only supports pure "
                f"global-attention layouts; {cfg.name} contains {sorted(bad)} "
                f"blocks whose state integrates pad tokens — prefill those "
                f"at exact length instead")
        lengths = jnp.asarray(lengths, jnp.int32)
    if par is not None and par.mesh is not None:
        h = par.seq_sharded(h)

    def prefill_block(kind, p, h):
        if kind in ("attn", "local_attn"):
            window = cfg.window if kind == "local_attn" else 0
            hn = L.apply_norm(cfg, p["norm1"], h)
            from repro.core import fpdt

            o = fpdt.fpdt_attention(cfg, par, p["attn"], hn,
                                    kind=attn_kind(cfg, par), window=window)
            h = h + o @ p["attn"]["wo"]
            # cache: recompute roped k/v (cheap vs attention)
            _, k, v = L.qkv_proj(cfg, p["attn"], hn)
            k = L.apply_rope(k, jnp.arange(s), cfg.rope_theta)
            W = min(cfg.window, max_len) if kind == "local_attn" else max_len
            ck = _attn_cache(cfg, b, W, h.dtype)
            take = min(W, s)
            pvec = jnp.arange(s - take, s)
            # ring slots MUST follow the decode invariant slot = pos % W —
            # writing the tail at slots 0..take-1 is only equivalent when
            # (s - take) % W == 0, and otherwise decode evicts the wrong
            # entry (a position still inside the window)
            slots = pvec % W if kind == "local_attn" else pvec
            kp = jnp.broadcast_to(pvec[None], (b, take))
            if lengths is not None:  # mask pad-token slots as never-filled
                kp = jnp.where(kp < lengths[:, None], kp, -1)
            cache = {
                "k": ck["k"].at[:, slots].set(k[:, s - take:].astype(ck["k"].dtype)),
                "v": ck["v"].at[:, slots].set(v[:, s - take:].astype(ck["v"].dtype)),
                "kpos": ck["kpos"].at[:, slots].set(kp),
            }
            hn2 = L.apply_norm(cfg, p["norm2"], h)
            if cfg.num_experts:
                from repro.models import moe as MOE

                y, _ = MOE.moe_ffn_chunked(cfg, p["moe"], hn2, cfg.mlp_chunks)
            else:
                y = L.mlp_chunked(cfg, p["mlp"], hn2, cfg.mlp_chunks)
            return h + y, cache
        if kind == "ssm":
            hn = L.apply_norm(cfg, p["norm"], h)
            y, st = M.mamba_mixer(cfg, p["mixer"], hn, None, None)
            return h + y, st
        if kind == "rglru":
            hn = L.apply_norm(cfg, p["norm1"], h)
            y, st = R.rglru_mixer(cfg, p["mixer"], hn, None, None, scan_impl="xla")
            h = h + y
            hn2 = L.apply_norm(cfg, p["norm2"], h)
            return h + L.mlp_chunked(cfg, p["mlp"], hn2, cfg.mlp_chunks), st
        raise ValueError(kind)

    def cycle_body(h, cyc_p):
        caches = {}
        for i, kind in enumerate(pat):
            h, c = prefill_block(kind, cyc_p[f"pos{i}"], h)
            caches[f"pos{i}"] = c
        if par is not None and par.mesh is not None:
            h = par.seq_sharded(h)
        return h, caches

    h, cycle_caches = jax.lax.scan(cycle_body, h, params["cycles"])
    cache = dict(cycle_caches)
    if tail:
        tcaches = []
        for i, kind in enumerate(tail):
            h, c = prefill_block(kind, params["tail"][i], h)
            tcaches.append(c)
        cache["tail"] = tcaches
    h = L.apply_norm(cfg, params["final_norm"], h)
    last = h[:, -1] if lengths is None else h[jnp.arange(b), lengths - 1]
    logits = (last @ head_matrix(cfg, params)).astype(jnp.float32)
    return logits, cache
