"""Two-tier paged KV-cache pool with radix-tree prefix reuse.

The dense serve engine gives every slot a ``[b, max_len]`` cache row:
memory scales with ``slots x worst-case-prompt`` even when most rows are
short, ``reset_slot`` invalidates a whole row, and two requests sharing a
long prefix prefill it twice.  This module replaces the per-slot rows of
full-attention blocks with one slot-SHARED pool of fixed-size pages
(``models/serve.py::init_paged_cache``) plus host-side metadata:

* ``PagePool`` — free-list allocator with refcounts over ``n_pages``
  physical pages; a page is free iff its refcount is 0.
* ``RadixTree`` — prefix index at full-page granularity, keyed on the
  page's token content (one tree node per page; the path from the root
  spells the prefix, so lookups chain page keys exactly like a rolling
  hash).  Matching a new prompt maps its longest previously-prefilled
  full-page prefix to the physical pages that already hold its KV —
  copy-free sharing; the tree holds one refcount per page it references,
  so cached prefixes survive the requests that created them until evicted
  (LRU leaves first, and only pages nobody else maps).
* ``SpillPool`` — host-resident spill tier behind the radix tree
  (Mooncake-style tiered KV): with ``spill_pages > 0`` eviction DEMOTES a
  cold prefix page's payload host-side instead of dropping it, the next
  prefix hit PROMOTES it back into a fresh device page through one jitted
  ``promote_page`` scatter, and ``save``/``restore`` persist the whole
  prefix cache (tree + payloads) across engine restarts — a second
  process serving the same system prompt starts with radix hits, not
  cold prefills.
* ``PagedCacheManager`` — per-slot page tables (``[slots, max_pages]``
  int32; ``-1`` = unmapped, FREE rows point at the trash page), admission
  control (a request's full page reserve is allocated up front, so the
  table is invariant across a whole segment and pool exhaustion is a
  clean admit-time error, never a mid-flight one), copy-on-write
  (``ensure_writable``: a shared page is copied before its owner may
  write, so no page is ever reachable from two tables once they diverge),
  and the radix publish/evict lifecycle.  Pure host metadata — device
  work (page invalidation, COW copies) is returned as work lists the
  engine dispatches through its jitted ``paged_reset``/``copy_page``
  programs.
* ``PagedServeEngine`` — ``ServeEngine`` subclass: the fused mixed-step
  scheduler is untouched; attention simply gathers/scatters K/V through
  the page table (``models/serve.py`` paged twins, host-streamed page by
  page via ``fori_double_buffered`` when ``n_host_chunks > 0``), admit
  maps radix-hit pages and starts prefill AFTER them (a shared prefix is
  never recomputed), and release returns the slot's pages to the pool.

See ``docs/serving.md`` (paged-pool section) for the lifecycle diagram.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.core.parallel import ParallelContext
from repro.models import serve as SV
from repro.models.transformer import layout_of
from repro.runtime import decode_loop as DL
from repro.runtime import telemetry as TM

Params = Dict[str, Any]


class PoolExhausted(ValueError):
    """No free pages for an admission.  A ``ValueError`` so it surfaces
    cleanly when raised to callers, but catchable separately so the engine
    can defer a request while other slots still hold pages."""


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------


class PagePool:
    """Free-list page allocator with refcounts.

    Invariants (property-tested in ``tests/test_paged.py``):
      * a page is on the free list iff its refcount is 0;
      * ``alloc`` never hands out a page twice without an intervening
        release to zero;
      * ``share``/``release`` only touch live (refcount > 0) pages.
    """

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        self.n_pages = n_pages
        self.refcount = np.zeros(n_pages, np.int64)
        self._free = list(range(n_pages - 1, -1, -1))  # stack: page 0 first

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise PoolExhausted(f"all {self.n_pages} pages in use")
        pid = self._free.pop()
        self.refcount[pid] = 1
        return pid

    def share(self, pid: int) -> None:
        if self.refcount[pid] <= 0:
            raise ValueError(f"share of free page {pid}")
        self.refcount[pid] += 1

    def release(self, pid: int) -> None:
        rc = int(self.refcount[pid])
        if rc <= 0:
            raise ValueError(f"release of free page {pid}")
        self.refcount[pid] = rc - 1
        if rc == 1:
            self._free.append(pid)


# ---------------------------------------------------------------------------
# host-resident spill tier
# ---------------------------------------------------------------------------


class SpillPool:
    """Host-resident spill tier: ``n_spill`` page-payload slots in plain
    host (numpy) buffers, one buffer per pool leaf, allocated lazily from
    the first demoted page's rows so the pool knows nothing about model
    shapes.  The radix tree demotes cold evicted pages here instead of
    dropping them and promotes them back into device pages on the next
    prefix hit; payloads round-trip ``RadixTree.save``/``restore`` so a
    prefix cache survives engine restarts.  Refcount-free by design: the
    tree is the sole owner of every spill entry."""

    def __init__(self, n_spill: int):
        if n_spill < 1:
            raise ValueError(f"n_spill must be >= 1, got {n_spill}")
        self.n_spill = n_spill
        self._free = list(range(n_spill - 1, -1, -1))  # stack: slot 0 first
        self.data: Dict[str, np.ndarray] = {}  # leaf path -> [n_spill, ...]
        self.demotions = 0  # payload writes to date (demotes + restores)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.n_spill - len(self._free)

    def alloc(self) -> int:
        """A free spill slot, or ``-1`` when the tier is full — the caller
        then falls back to dropping the page, so spill never blocks
        eviction."""
        return self._free.pop() if self._free else -1

    def free(self, sid: int) -> None:
        if sid in self._free:
            raise ValueError(f"free of unallocated spill slot {sid}")
        self._free.append(sid)

    def write(self, sid: int, rows: Dict[str, np.ndarray]) -> None:
        """Store one page's rows (``models/serve.py::page_rows`` keys)."""
        for k, row in rows.items():
            buf = self.data.get(k)
            if buf is None:
                row = np.asarray(row)
                buf = np.zeros((self.n_spill, *row.shape), row.dtype)
                self.data[k] = buf
            buf[sid] = row
        self.demotions += 1

    def read(self, sid: int) -> Dict[str, np.ndarray]:
        return {k: buf[sid] for k, buf in self.data.items()}


# ---------------------------------------------------------------------------
# radix tree (full-page prefix index)
# ---------------------------------------------------------------------------


def _page_key(tokens) -> bytes:
    """Exact content key of one page of prompt tokens (the dict lookup
    hashes it, chaining parent keys along the tree path)."""
    return np.asarray(tokens, np.int32).tobytes()


class _Node:
    __slots__ = ("children", "parent", "key", "page", "spill", "last_used")

    def __init__(self, parent: Optional["_Node"] = None,
                 key: Optional[bytes] = None):
        self.children: Dict[bytes, "_Node"] = {}
        self.parent, self.key = parent, key
        self.page = -1   # device page, or -1 when demoted to the spill tier
        self.spill = -1  # spill slot, or -1 when device-resident
        self.last_used = 0


class RadixTree:
    """Full-page-granularity prefix index over a ``PagePool``.

    Only FULL pages are indexed — a prompt's partial tail page is private
    to its slot, so shared pages are immutable by construction (writes
    only ever target the suffix a request prefills itself, or go through
    copy-on-write).  The tree owns one refcount per referenced page.

    With a :class:`SpillPool`, every node is either device-resident
    (``page >= 0``) or spilled (``spill >= 0``); along any root-to-leaf
    path the resident nodes form a prefix (demotion runs suffix-first,
    promotion re-admits a whole matched chain), so the device tier is
    always a connected top slice of the tree.  Demotion copies a page's
    payload host-side through ``read_page`` — set by the engine, it
    fetches one physical page of the live pool — at evict time, BEFORE
    the freed device page can be reallocated.
    """

    def __init__(self, page_size: int, pool: PagePool,
                 spill: Optional[SpillPool] = None):
        self.page_size, self.pool = page_size, pool
        self.spill = spill
        self.read_page: Optional[Callable[[int], Dict[str, np.ndarray]]] = None
        self.root = _Node()
        self._clock = 0
        self.pages = 0  # device pages the tree currently references
        # set by the owning engine: demote/evict decisions trace here
        self.telemetry: Optional[TM.Telemetry] = None

    @property
    def spilled(self) -> int:
        return self.spill.used_count if self.spill is not None else 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match_nodes(self, tokens: Sequence[int]) -> List[_Node]:
        """Node chain of the longest already-indexed full-page prefix of
        ``tokens`` — entries may be device-resident (``page >= 0``) or
        spilled (``spill >= 0``; the manager promotes those at admit).
        Touches LRU stamps; takes NO refcounts — the caller shares what it
        actually maps."""
        ps = self.page_size
        node, out, t = self.root, [], self._tick()
        for i in range(len(tokens) // ps):
            child = node.children.get(_page_key(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            child.last_used = t
            out.append(child)
            node = child
        return out

    def match(self, tokens: Sequence[int]) -> List[int]:
        """Physical pages holding the longest already-indexed full-page
        prefix of ``tokens`` (device view: spilled entries report -1)."""
        return [nd.page for nd in self.match_nodes(tokens)]

    def insert(self, tokens: Sequence[int], pids: Sequence[int]) -> int:
        """Index ``pids`` as holding the leading full pages of ``tokens``.
        Existing resident nodes win (first prefill published; contents are
        identical by construction) and take no extra reference; an
        existing SPILLED twin is re-pointed at the freshly prefilled
        device page instead (a free promotion — the host copy is dropped).
        Returns how many pages were newly device-indexed."""
        ps = self.page_size
        node, t, added = self.root, self._tick(), 0
        for i, pid in enumerate(pids):
            key = _page_key(tokens[i * ps:(i + 1) * ps])
            child = node.children.get(key)
            if child is None:
                child = _Node(parent=node, key=key)
                child.page = int(pid)
                node.children[key] = child
                self.pool.share(int(pid))
                self.pages += 1
                added += 1
            elif child.page < 0:
                child.page = int(pid)
                self.pool.share(int(pid))
                self.pages += 1
                if self.spill is not None:
                    self.spill.free(child.spill)
                child.spill = -1
                added += 1
            child.last_used = t
            node = child
        return added

    def promote(self, nd: _Node, pid: int) -> int:
        """Re-admit spilled node ``nd`` at device page ``pid`` (the tree
        takes over the caller's freshly allocated reference).  Returns the
        spill slot whose payload must be scattered into ``pid`` — the
        caller frees it only AFTER that copy is dispatched."""
        sid = nd.spill
        nd.page, nd.spill = int(pid), -1
        self.pages += 1
        return sid

    def _evictable(self, nd: _Node) -> bool:
        """Device-resident, tree-only reference, and no device-resident
        child — residency is a path prefix (see class docstring), so
        childless-in-the-device-tier means leaf of the device tier."""
        return (nd.page >= 0
                and int(self.pool.refcount[nd.page]) == 1
                and not any(c.page >= 0 for c in nd.children.values()))

    def _evictable_leaves(self) -> List[_Node]:
        out, stack = [], [self.root]
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            if nd is not self.root and self._evictable(nd):
                out.append(nd)
        return out

    def evict(self, n_pages: int) -> int:
        """Free up to ``n_pages`` least-recently-used device pages whose
        only reference is the tree's own, in ONE pass: the evictable set
        is collected once and maintained incrementally on a heap (a parent
        joins when its last device-resident child leaves) instead of
        re-walking the whole tree per freed page.  Candidate stamps are
        unique — equal stamps only occur along one ancestor chain, never
        between two simultaneously evictable nodes — so the heap
        reproduces the old rescan-per-page order exactly (property-pinned
        in ``tests/test_paged.py``).

        With a spill tier, each victim's payload is demoted host-side
        through ``read_page`` (synchronously — the freed device page may
        be reallocated and overwritten within the same admit) and the node
        survives as a spilled entry; without one, or when the tier is
        full, the node is dropped as before (a node whose spilled children
        would be stranded by a drop stays resident instead).  Returns
        device pages actually freed."""
        heap: List[Tuple[int, int, _Node]] = []
        n = 0

        def push(nd: _Node) -> None:
            nonlocal n
            if nd is not self.root and self._evictable(nd):
                n += 1
                heapq.heappush(heap, (nd.last_used, n, nd))

        stack = [self.root]
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            push(nd)
        can_spill = self.spill is not None and self.read_page is not None
        freed = 0
        while freed < n_pages and heap:
            _, _, victim = heapq.heappop(heap)
            if not self._evictable(victim):
                continue  # stale heap entry
            sid = self.spill.alloc() if can_spill else -1
            if sid >= 0:
                self.spill.write(sid, self.read_page(victim.page))
                if self.telemetry is not None:
                    self.telemetry.registry.counter("pool_demotions").inc()
                    self.telemetry.event("pool.demote", page=int(victim.page),
                                         spill=int(sid))
                self.pool.release(victim.page)
                victim.page, victim.spill = -1, sid
            elif victim.children:
                continue  # drop would strand spilled descendants: keep
            else:
                if self.telemetry is not None:
                    self.telemetry.registry.counter("pool_evictions").inc()
                    self.telemetry.event("pool.evict", page=int(victim.page))
                del victim.parent.children[victim.key]
                self.pool.release(victim.page)
            self.pages -= 1
            freed += 1
            push(victim.parent)
        return freed

    # -- persistence -----------------------------------------------------
    def save(self, path: str, read_page: Optional[Callable] = None) -> int:
        """Serialize the whole prefix cache — tree structure, LRU stamps,
        and every indexed page's KV payload (spilled entries straight from
        the spill tier, device-resident ones fetched through
        ``read_page``) — into one ``.npz``.  Format (docs/serving.md):
        ``page_size`` scalar, ``parent`` [N] int64 node-list indices
        (-1 = root; parents always precede children), ``tokens`` [N, ps]
        int32 page keys, ``last_used`` [N] int64, plus one
        ``rows/<leaf path>`` [N, ...] array per pool leaf.  Returns the
        number of pages saved."""
        read_page = read_page or self.read_page
        order: List[_Node] = []
        index: Dict[int, int] = {id(self.root): -1}
        stack = [self.root]
        while stack:
            nd = stack.pop()
            for c in nd.children.values():
                index[id(c)] = len(order)
                order.append(c)
                stack.append(c)
        ps = self.page_size
        payload: Dict[str, List[np.ndarray]] = {}
        for nd in order:
            if nd.page >= 0:
                if read_page is None:
                    raise ValueError(
                        "save needs a read_page callback to fetch "
                        "device-resident pages (the engine's page reader)")
                rows = read_page(nd.page)
            else:
                rows = self.spill.read(nd.spill)
            for k, v in rows.items():
                payload.setdefault(k, []).append(np.asarray(v))
        # extension dtypes (bfloat16, fp8) round-trip npz as opaque void —
        # store them bit-cast to a same-width uint plus the dtype name
        stacks = {}
        for k, v in payload.items():
            arr = np.stack(v)
            if arr.dtype.kind not in "fiub":
                stacks[f"dtype/{k}"] = np.str_(arr.dtype.name)
                arr = arr.view(np.dtype(f"u{arr.dtype.itemsize}"))
            stacks[f"rows/{k}"] = arr
        np.savez(
            path, page_size=np.int64(ps),
            parent=np.array([index[id(nd.parent)] for nd in order], np.int64),
            tokens=(np.stack([np.frombuffer(nd.key, np.int32) for nd in order])
                    if order else np.zeros((0, ps), np.int32)),
            last_used=np.array([nd.last_used for nd in order], np.int64),
            **stacks)
        return len(order)

    def restore(self, path: str) -> int:
        """Load a saved prefix cache.  Every restored page lands in the
        SPILL tier — no device pages are touched; payloads promote on
        their first prefix hit — and live entries win over colliding saved
        ones.  Entries beyond the tier's free slots are dropped (children
        of a dropped node follow it).  Returns pages actually restored."""
        if self.spill is None:
            raise ValueError(
                "restore needs a spill tier (spill_pages > 0): restored "
                "pages are host-resident until their first prefix hit")
        data = np.load(path)
        ps = int(data["page_size"])
        if ps != self.page_size:
            raise ValueError(f"kv store was saved at page_size={ps}; this "
                             f"pool uses page_size={self.page_size}")
        parents, tokens = data["parent"], data["tokens"]
        stamps = data["last_used"]
        row_keys = [k for k in data.files if k.startswith("rows/")]
        dtypes = {}
        for k in row_keys:
            dk = "dtype/" + k[len("rows/"):]
            if dk in data.files:  # bit-cast extension dtype (e.g. bfloat16)
                import ml_dtypes  # jax dependency

                dtypes[k] = np.dtype(getattr(ml_dtypes, str(data[dk])))

        def rows_at(i: int) -> Dict[str, np.ndarray]:
            return {k[len("rows/"):]:
                    (data[k][i].view(dtypes[k]) if k in dtypes else data[k][i])
                    for k in row_keys}
        nodes: List[Optional[_Node]] = [None] * len(parents)
        restored = 0
        for i in range(len(parents)):
            pnode = self.root if parents[i] < 0 else nodes[int(parents[i])]
            if pnode is None:  # parent dropped/unrestorable: drop subtree
                continue
            key = tokens[i].tobytes()
            child = pnode.children.get(key)
            if child is not None:  # live entry wins over the stored twin
                nodes[i] = child
                continue
            sid = self.spill.alloc()
            if sid < 0:
                continue  # tier full: drop (descendants follow)
            self.spill.write(sid, rows_at(i))
            child = _Node(parent=pnode, key=key)
            child.spill = sid
            child.last_used = int(stamps[i])
            pnode.children[key] = child
            nodes[i] = child
            restored += 1
        if len(stamps):
            self._clock = max(self._clock, int(stamps.max()) + 1)
        return restored


# ---------------------------------------------------------------------------
# page tables + admission + COW
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AdmitPlan:
    """Host-side result of admitting one request: what the engine must
    dispatch to the device before the slot's first segment.  Demotions
    never appear here — eviction copies payloads host-side synchronously
    (the freed page may be reallocated within this very plan); promotions
    are work lists because the scatter targets freshly allocated device
    pages this plan owns."""

    resume: int                        # prompt tokens already cached (skip)
    fresh_pages: List[int]             # newly allocated -> need invalidation
    cow: List[Tuple[int, int]]         # (src, dst) page copies to dispatch
    hit_pages: int                     # full pages served from the tree
    # (spill slot, dst page, keep-below offset) scatters to dispatch:
    # promote-from-spill re-admissions (keep = page_size) and the
    # spilled-COW variant (keep = resume % page_size, tail recomputed)
    promote: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list)
    # spill slots to return once the promote scatters are dispatched
    free_spill: List[int] = dataclasses.field(default_factory=list)


class PagedCacheManager:
    """Page tables, admission control, COW, and the radix lifecycle.

    The manager never touches device arrays: ``admit`` returns an
    ``AdmitPlan`` naming the pages to invalidate/copy, and ``table`` is a
    plain int32 numpy array the engine ships with every dispatch.  A
    request's worst-case page reserve (``ceil((plen + budget) / ps)``) is
    allocated at admit, so the table is segment-invariant and the pool can
    never run dry mid-flight — exhaustion is an admit-time
    ``PoolExhausted``.
    """

    def __init__(self, n_pages: int, page_size: int, use_radix: bool = True,
                 spill_pages: int = 0):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.pool = PagePool(n_pages)
        self.page_size = page_size
        self.spill = (SpillPool(spill_pages)
                      if use_radix and spill_pages > 0 else None)
        self.radix = (RadixTree(page_size, self.pool, spill=self.spill)
                      if use_radix else None)
        self.trash = n_pages  # physical index of the FREE-slot write sink
        self.table: Optional[np.ndarray] = None
        self._slot_pages: List[List[int]] = []

    def set_page_reader(self, read_page: Callable[[int], Dict[str, np.ndarray]]
                        ) -> None:
        """Register the engine's device->host page fetch
        (``models/serve.py::page_rows`` over the live pool) — eviction
        demotes and ``save`` serializes resident pages through it."""
        if self.radix is not None:
            self.radix.read_page = read_page

    def begin(self, slots: int, max_pages: int) -> None:
        """Start a workload: fresh all-FREE tables.  Slots a previous
        workload left admitted (an exception mid-``generate`` — the engine
        is long-lived, so it must not stay wedged) are released here;
        radix-indexed pages persist either way."""
        for s, pages in enumerate(self._slot_pages):
            if pages:
                self.release(s)
        self.table = np.full((slots, max_pages), self.trash, np.int32)
        self._slot_pages = [[] for _ in range(slots)]

    # -- admission -------------------------------------------------------
    def admit(self, slot: int, tokens: Sequence[int], budget: int,
              label: str = "request") -> AdmitPlan:
        """Map slot ``slot`` for a prompt of ``tokens`` plus ``budget``
        generated tokens.  Radix-matched prefix pages are mapped shared
        (copy-free); the rest of the reserve is allocated fresh.  When the
        match covers the whole prompt, the last matched page is taken via
        copy-on-write instead — the resumed prefill must recompute (and
        rewrite) the final token to produce first-token logits, and a
        shared page must never be written."""
        if self._slot_pages[slot]:
            raise ValueError(f"slot {slot} admitted twice without release")
        ps = self.page_size
        plen = len(tokens)
        need = max(-(-(plen + budget) // ps), 1)
        if need > self.table.shape[1]:
            raise ValueError(
                f"{label}: needs {need} pages ({plen} prompt + {budget} new "
                f"tokens at page_size={ps}) but the table is only "
                f"{self.table.shape[1]} pages wide")
        nodes = (self.radix.match_nodes(tokens)
                 if self.radix is not None else [])
        m = len(nodes)
        resume = min(m * ps, max(plen - 1, 0))
        n_shared = m if resume == m * ps else m - 1
        shared = nodes[:n_shared]
        cow_src = nodes[n_shared:]  # 0 or 1 node (the full-cover case)
        # take refs on every RESIDENT matched page first — the shared ones
        # we keep AND the COW source (its protective ref is dropped once
        # the copy pair is recorded) — so eviction can't free a page the
        # plan reads.  Spilled entries have no device page to protect, and
        # eviction never touches the spill tier.
        for nd in (*shared, *cow_src):
            if nd.page >= 0:
                self.pool.share(nd.page)
        # device pages to allocate: the non-shared remainder of the
        # reserve, plus one promote target per spilled shared page
        fresh_needed = (need - n_shared
                        + sum(1 for nd in shared if nd.page < 0))
        if self.pool.free_count < fresh_needed and self.radix is not None:
            self.radix.evict(fresh_needed - self.pool.free_count)
        if self.pool.free_count < fresh_needed:
            for nd in (*shared, *cow_src):
                if nd.page >= 0:
                    self.pool.release(nd.page)
            raise PoolExhausted(
                f"{label}: needs {fresh_needed} free pages ({plen} prompt + "
                f"{budget} new tokens at page_size={ps}, {n_shared} prefix "
                f"pages shared) but only {self.pool.free_count} of "
                f"{self.pool.n_pages} are free")
        promote: List[Tuple[int, int, int]] = []
        free_spill: List[int] = []
        pids: List[int] = []
        for nd in shared:
            if nd.page < 0:  # spilled prefix page: promote back on-device
                pid = self.pool.alloc()        # becomes the tree's reference
                sid = self.radix.promote(nd, pid)
                promote.append((sid, pid, ps))  # keep the whole page
                free_spill.append(sid)
                self.pool.share(pid)           # the slot's reference
            pids.append(nd.page)
        cow: List[Tuple[int, int]] = []
        if cow_src:
            nd = cow_src[0]
            dst = self.pool.alloc()
            if nd.page < 0:
                # spilled COW source: scatter the payload STRAIGHT into the
                # slot's private dst page (the tree's copy stays spilled)
                promote.append((nd.spill, dst, resume % ps))
            else:
                cow.append((nd.page, dst))
                self.pool.release(nd.page)  # drop the protective ref
            pids.append(dst)
        fresh = [self.pool.alloc() for _ in range(need - len(pids))]
        pids.extend(fresh)
        self.table[slot, :] = -1
        self.table[slot, :need] = pids
        self._slot_pages[slot] = pids
        return AdmitPlan(resume=resume, fresh_pages=fresh, cow=cow,
                         hit_pages=m, promote=promote, free_spill=free_spill)

    def release(self, slot: int) -> None:
        """Return the slot's pages (tree-shared ones survive via their
        tree refcount) and park the row on the trash page."""
        for pid in self._slot_pages[slot]:
            self.pool.release(pid)
        self._slot_pages[slot] = []
        self.table[slot, :] = self.trash

    # -- copy-on-write ---------------------------------------------------
    def ensure_writable(self, slot: int, logical_j: int
                        ) -> Optional[Tuple[int, int]]:
        """Make logical page ``logical_j`` of ``slot`` exclusively owned.
        Returns the ``(src, dst)`` device copy to dispatch when the page
        was shared (after which no page is reachable from two tables),
        ``None`` when it already was exclusive."""
        pid = int(self.table[slot, logical_j])
        if pid < 0 or pid == self.trash:
            raise ValueError(f"slot {slot} logical page {logical_j} unmapped")
        if int(self.pool.refcount[pid]) <= 1:
            return None
        dst = self.pool.alloc()
        self.pool.release(pid)
        self.table[slot, logical_j] = dst
        self._slot_pages[slot][logical_j] = dst
        return pid, dst

    # -- radix lifecycle -------------------------------------------------
    def complete_prefill(self, slot: int, tokens: Sequence[int]) -> int:
        """Prefill finished: publish the prompt's full pages so future
        requests sharing the prefix map them copy-free."""
        if self.radix is None:
            return 0
        full = len(tokens) // self.page_size
        if not full:
            return 0
        return self.radix.insert(list(tokens)[: full * self.page_size],
                                 self._slot_pages[slot][:full])

    @property
    def pages_in_use(self) -> int:
        return self.pool.used_count

    @property
    def spilled_pages(self) -> int:
        return self.spill.used_count if self.spill is not None else 0

    # -- persistence -----------------------------------------------------
    def save(self, path: str,
             read_page: Optional[Callable] = None) -> int:
        """Persist the prefix cache (radix tree + page payloads) to
        ``path``; see ``RadixTree.save`` for the format."""
        if self.radix is None:
            raise ValueError("save: this pool has no radix prefix cache "
                             "(use_radix=False)")
        return self.radix.save(path, read_page)

    def restore(self, path: str) -> int:
        """Load a persisted prefix cache into the spill tier (requires
        ``spill_pages > 0``); pages promote on their first prefix hit."""
        if self.radix is None:
            raise ValueError("restore: this pool has no radix prefix cache "
                             "(use_radix=False)")
        return self.radix.restore(path)


# ---------------------------------------------------------------------------
# jitted page maintenance programs
# ---------------------------------------------------------------------------


def _leaf_names(path) -> List[str]:
    return [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]


def paged_reset(cache: Params, i, page_ids: jnp.ndarray) -> Params:
    """Slot + page invalidation in one program: slot ``i``'s per-slot rows
    reset exactly like ``reset_slot`` (dense ``kpos`` -> -1, recurrent
    state -> 0), and the pool's ``pkpos`` rows at ``page_ids`` go to -1 —
    newly allocated pages may hold a previous owner's entries, which must
    not alias the new sequence's positions.  ``page_ids`` is fixed-width;
    pad with any out-of-range id (they scatter with ``mode="drop"``)."""
    cache = DL.reset_slot(cache, i)

    def fix(path, leaf):
        names = _leaf_names(path)
        if names[-1] != "pkpos":
            return leaf
        if names[0] == "tail":
            return leaf.at[page_ids].set(-1, mode="drop")
        return leaf.at[:, page_ids].set(-1, mode="drop")

    return jax.tree_util.tree_map_with_path(fix, cache)


def pool_kv(cache: Params, keep: bool) -> Params:
    """``cache`` with only (``keep=True``) or without (``False``) the
    pool's ``pk``/``pv`` leaves; the dropped leaves become ``None``.
    Programs that never touch the pool K/V (``paged_reset``) run on the
    rest, so a host-parked pool is not passed through them."""
    def pick(path, leaf):
        return leaf if (_leaf_names(path)[-1] in ("pk", "pv")) == keep else None

    return jax.tree_util.tree_map_with_path(pick, cache)


def join_kv(kv: Params, rest: Params) -> Params:
    """Inverse of ``pool_kv``: merge the two halves of a cache."""
    return jax.tree.map(lambda a, b: b if a is None else a, kv, rest,
                        is_leaf=lambda x: x is None)


def copy_page(cache: Params, src, dst, drop_from, *,
              par: Optional[ParallelContext] = None) -> Params:
    """Copy physical page ``src`` -> ``dst`` in every attention layer (the
    COW primitive).  Entries at in-page offsets ``>= drop_from`` are
    invalidated in the copy: they are the COW'd tail the resumed prefill
    will recompute and rewrite, and leaving them valid would double-count
    against the chunk program's own intra-window keys.  ``par`` places a
    host-parked pool (``None``: the pool is in device memory)."""
    keep = None

    def fix(path, leaf):
        nonlocal keep
        names = _leaf_names(path)
        kind = names[-1]
        if kind in ("pk", "pv"):
            return SV.set_page(par, leaf, dst, SV.get_page(par, leaf, src))
        if kind != "pkpos":
            return leaf
        stacked = names[0] != "tail"
        row = leaf[:, src] if stacked else leaf[src]
        if keep is None:
            keep = jnp.arange(leaf.shape[-1]) < drop_from
        row = jnp.where(keep, row, -1)
        return leaf.at[:, dst].set(row) if stacked else leaf.at[dst].set(row)

    return jax.tree_util.tree_map_with_path(fix, cache)


def promote_page(cache: Params, dst, rows: Dict[str, jnp.ndarray],
                 keep_below, *, par: Optional[ParallelContext] = None) -> Params:
    """Scatter one spilled page's host rows into physical page ``dst`` —
    the spill tier's re-admit primitive, the exact inverse of the
    ``models/serve.py::page_rows`` demotion gather (``rows`` is keyed by
    ``pool_leaf_key``).  ``pkpos`` entries at in-page offsets
    ``>= keep_below`` are invalidated in the scatter: a plain re-admit
    passes ``page_size`` (keep everything), the spilled-COW path passes
    the resume offset so the tail the resumed prefill recomputes is not
    double-counted (mirroring ``copy_page``'s ``drop_from``).  ``par``
    places a host-parked pool."""
    keep = None

    def fix(path, leaf):
        nonlocal keep
        names = _leaf_names(path)
        kind = names[-1]
        if kind not in ("pk", "pv", "pkpos"):
            return leaf
        row = jnp.asarray(rows[SV.pool_leaf_key(path)])
        if kind != "pkpos":
            return SV.set_page(par, leaf, dst, row)
        if keep is None:
            keep = jnp.arange(leaf.shape[-1]) < keep_below
        row = jnp.where(keep, row, -1)
        stacked = names[0] != "tail"
        return leaf.at[:, dst].set(row) if stacked else leaf.at[dst].set(row)

    return jax.tree_util.tree_map_with_path(fix, cache)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class PagedServeEngine(DL.ServeEngine):
    """Continuous batching over the slot-shared paged pool.

    Same fused mixed-step scheduler as ``ServeEngine`` — the segment
    program just reads/writes attention K/V through the page table, so
    ``compiled_programs()`` stays a bounded set (one segment, one
    reset-and-invalidate, one COW copy, one promote-from-spill scatter)
    and program size is flat in ``n_pages`` (the pool only changes array
    DIMENSIONS; the page loop is ``fori_double_buffered`` over logical
    pages).  What changes is the slot lifecycle:

      admit   — radix-match the prompt, map shared prefix pages copy-free
                (prefill resumes AFTER them; spilled prefix pages are
                promoted back into fresh device pages first), allocate
                the rest of the worst-case reserve, invalidate fresh
                pages, dispatch COW copies and promote scatters.  A
                request that cannot fit defers while other slots hold
                pages and raises ``ValueError`` (naming it) when the pool
                could never take it.
      release — refcount-release the slot's pages; radix-published prefix
                pages survive for future requests (two-tier: with
                ``n_host_chunks > 0`` the pool itself is host-resident
                and pages stream device-ward inside attention).

    With ``spill_pages > 0`` eviction demotes cold radix pages into the
    host-resident :class:`SpillPool` instead of dropping them, and
    ``save_kv_store``/``restore_kv_store`` persist the prefix cache
    across engine restarts — a second process serving the same system
    prompt gets radix hits, not cold prefills.

    ``radix=True`` only takes effect for pure global-attention layouts:
    recurrent blocks (ssm/rglru/local_attn ring) integrate the whole
    prefix into per-slot state that a mapped page cannot restore, so
    prefix skipping would be silently wrong — those layouts still get the
    paged pool, just with ``resume = 0``.

    The pool (and its radix-indexed contents) persists across
    ``generate`` calls — a shared system prompt served in one workload is
    a prefix hit in the next.
    """

    def __init__(self, cfg: ModelConfig, params: Params, *, slots: int,
                 bucket: int, max_new_tokens: int, page_size: int = 16,
                 n_pages: int = 0, radix: bool = True, spill_pages: int = 0,
                 prefill_chunk: int = 0, n_host_chunks: int = 0,
                 sampling: DL.SamplingConfig = DL.GREEDY,
                 stop_tokens: Sequence[int] = (), pad_id: int = 0,
                 segment: int = 8, par: Optional[ParallelContext] = None):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = int(page_size)
        if n_pages <= 0:  # default: dense-equivalent capacity
            n_pages = slots * -(-(bucket + max_new_tokens) // self.page_size)
        self.n_pages = int(n_pages)
        pat, _, tail = layout_of(cfg)
        self.radix_enabled = bool(radix) and all(
            k == "attn" for k in (*pat, *tail))
        self.kv = PagedCacheManager(
            self.n_pages, self.page_size, use_radix=self.radix_enabled,
            spill_pages=spill_pages if self.radix_enabled else 0)
        self.kv.set_page_reader(self._read_page)
        self._pool_cache = SV.init_paged_cache(cfg, slots, self.n_pages,
                                               self.page_size)
        # freshest pool view for host-side page reads (demotion at evict
        # time, save_kv_store): re-pointed after every program that writes
        # the pool so read_page never sees a stale page payload
        self._cur_cache = self._pool_cache
        self._table_dev = None  # device copy, refreshed at admit/release
        self._inserted = [True] * slots
        super().__init__(cfg, params, slots=slots, bucket=bucket,
                         max_new_tokens=max_new_tokens,
                         prefill_chunk=prefill_chunk,
                         n_host_chunks=n_host_chunks, sampling=sampling,
                         stop_tokens=stop_tokens, pad_id=pad_id,
                         segment=segment, par=par)
        if self.kv.radix is not None:
            self.kv.radix.telemetry = self.telemetry
        if self.cp % self.page_size and self.page_size % self.cp:
            raise ValueError(
                f"prefill_chunk={self.cp} and page_size={self.page_size} "
                f"must divide one another: radix prefix hits resume prefill "
                f"at a page boundary, and only a mutually-dividing grid "
                f"keeps every chunk window inside the slot's allocated page "
                f"reserve")
        # two-tier placement: the pool's K/V live host-side and attention
        # streams pages device-ward
        self._pool_cache = self._offload_pool(self._pool_cache)
        self._cur_cache = self._pool_cache

    @property
    def _pool_par(self) -> Optional[ParallelContext]:
        """Placement of the host-parked pool; ``None`` when the pool is
        in device memory."""
        return self.par if self.n_host_chunks else None

    def _read_page(self, pid: int) -> Dict[str, np.ndarray]:
        """Fetch one physical page's K/V rows host-side (demotion + save)."""
        rows = self._page_rows(self._cur_cache, jnp.int32(pid))
        return {k: np.asarray(jax.device_get(v)) for k, v in rows.items()}

    # -- prefix-cache persistence ----------------------------------------
    # cumulative counters (kv_store_saved_pages / kv_store_restored_pages)
    # let the serve tier report how much failover recovery actually moved
    # through the store, across however many publish/restore rounds
    def save_kv_store(self, path: str) -> int:
        """Persist the radix tree + every cached page payload to ``path``."""
        n = self.kv.save(path, self._read_page)
        self.kv_store_saved_pages = getattr(
            self, "kv_store_saved_pages", 0) + n
        self.telemetry.registry.counter("kvstore_saved_pages").inc(n)
        self.telemetry.event("kvstore.save", pages=n)
        return n

    def restore_kv_store(self, path: str) -> int:
        """Load a persisted prefix cache into the spill tier (pages promote
        to device lazily, on their first radix hit)."""
        n = self.kv.restore(path)
        self.kv_store_restored_pages = getattr(
            self, "kv_store_restored_pages", 0) + n
        self.telemetry.registry.counter("kvstore_restored_pages").inc(n)
        self.telemetry.event("kvstore.restore", pages=n)
        return n

    def _offload_pool(self, cache):
        """Put a returned pool back where the programs take it — on a mesh
        the in-shardings, else the pool's K/V parked in the offload tier
        when the engine is host-streaming.  Called at init and after every
        program that returns the pool; a no-op where the output already
        is there.  A host-parked pool on a mesh splits its page rows over
        every axis (``SV.pool_host_spec``), which raises when it cannot."""
        if self._cache_sh is not None:
            return jax.device_put(cache, self._cache_sh)
        if self._pool_par is None:
            return cache
        # commit every leaf where it is: the parked K/V are committed, so
        # every program output is, and commitment is part of a program's
        # signature (an uncommitted first pool would compile twice)
        cache = jax.tree.map(lambda l: jax.device_put(l, l.sharding), cache)
        return SV.park_pool(self._pool_par, cache)

    # -- compiled programs ----------------------------------------------
    def _segment_shardings(self):
        """Pool-layout shardings over the CONCRETE pool (its shapes never
        change — capacity lives in the page table, not the arrays), plus a
        replicated page-table argument."""
        if self.par is None or self.par.mesh is None:
            return None
        return DL.segment_shardings(self.cfg, self.par, self._pool_cache,
                                    table=True,
                                    host_pool=self._pool_par is not None)

    def _build_programs(self) -> None:
        cfg, par, params = self.cfg, self.par, self.params

        def seg(cache, mode, tok, pos, key, rem, pfill, pend, plen, table,
                params):
            return DL.mixed_segment(cfg, par, params, cache, mode, tok, pos,
                                    key, rem, pfill, pend, plen,
                                    num_steps=self.segment,
                                    prefill_chunk=self.cp,
                                    n_host_chunks=self.n_host_chunks,
                                    sampling=self.sampling,
                                    stop_tokens=self._stop,
                                    pad_id=self.pad_id, table=table)

        tel = self.telemetry
        pool_par = self._pool_par
        copy = functools.partial(copy_page, par=pool_par)
        promote = functools.partial(promote_page, par=pool_par)
        self._page_rows = jax.jit(functools.partial(SV.page_rows, pool_par))
        sh = self._segment_shardings()
        if sh is None:
            self._cache_sh = None
            self._seg_params = params
            self._segment = jax.jit(DL.per_engine(seg, tel, "segment"))
            self._reset = jax.jit(DL.per_engine(paged_reset, tel, "reset"))
            self._copy = jax.jit(DL.per_engine(copy, tel, "copy"))
            self._promote = jax.jit(DL.per_engine(promote, tel, "promote"))
        else:
            # page copy/COW become sharded programs over the same pool
            # layout — each device moves only its own head (or in-page)
            # slice, no gather to one device.  Reset never sees the pool
            # K/V (``pool_kv``); with a host-parked pool the programs leave
            # their output placement to the partitioner (see
            # ``DL.segment_shardings``) and ``_offload_pool`` restores it
            in_sh, out_sh = sh
            csh, r = in_sh[0], par.ns()
            osh = None if out_sh is None else csh
            rest = pool_kv(csh, keep=False)
            self._cache_sh = csh
            self._seg_params = jax.device_put(params, r)
            self._segment = jax.jit(DL.per_engine(seg, tel, "segment"),
                                    in_shardings=in_sh,
                                    out_shardings=out_sh)
            self._reset = jax.jit(DL.per_engine(paged_reset, tel, "reset"),
                                  in_shardings=(rest, r, r),
                                  out_shardings=rest)
            self._copy = jax.jit(DL.per_engine(copy, tel, "copy"),
                                 in_shardings=(csh, r, r, r),
                                 out_shardings=osh)
            # the promoted rows dict gets `r` as a pytree PREFIX: every
            # host-staged row enters replicated, the scatter re-shards it
            # into the pool's own layout
            self._promote = jax.jit(DL.per_engine(promote, tel, "promote"),
                                    in_shardings=(csh, r, r, r),
                                    out_shardings=osh)
            # commit the persistent pool to its sharding NOW: the first
            # admit otherwise sees uncommitted arrays and compiles a second
            # reset signature, breaking the bounded-program guarantee
            self._pool_cache = jax.device_put(self._pool_cache, csh)

    def compiled_programs(self) -> Dict[str, int]:
        return {"segment": self._segment._cache_size(),
                "reset": self._reset._cache_size(),
                "copy": self._copy._cache_size(),
                "promote": self._promote._cache_size()}

    # -- slot lifecycle --------------------------------------------------
    def _begin(self, B: int, P: int, S: int):
        max_pages = -(-(P + self.max_new) // self.page_size)
        self.kv.begin(B, max_pages)
        self._table_dev = None
        self._inserted = [True] * B
        self.last_stats.update({
            "page_size": self.page_size, "n_pages": self.n_pages,
            "max_pages": max_pages, "radix": self.radix_enabled,
            "prompt_tokens": 0, "prefilled_tokens": 0,
            "prefix_hit_tokens": 0, "cow_copies": 0, "deferrals": 0,
            "pages_peak": 0, "radix_pages": 0,
            "spill_pages":
                0 if self.kv.spill is None else self.kv.spill.n_spill,
            "spill_promotes": 0, "spilled_pages": self.kv.spilled_pages,
        })
        return self._pool_cache

    def _admit(self, cache, s: int, idx: int, prompt, active: bool,
               budget: Optional[int] = None):
        st = self.last_stats
        budget = self.max_new if budget is None else int(budget)
        self._cur_cache = cache  # eviction may demote: read the live pool
        try:
            plan = self.kv.admit(s, list(prompt), budget,
                                 label=f"request {idx}")
        except PoolExhausted as e:
            if active:  # running slots will release pages; retry next round
                st["deferrals"] += 1
                self.telemetry.event("pool.defer", request=idx, slot=s)
                return None
            raise ValueError(str(e)) from None
        ids = np.full(self.n_pages, self.n_pages + 1, np.int32)  # pad -> OOB
        ids[: len(plan.fresh_pages)] = plan.fresh_pages
        cache = join_kv(pool_kv(cache, keep=True),
                        self._reset(pool_kv(cache, keep=False), s,
                                    jnp.asarray(ids)))
        for sid, dst, keep in plan.promote:
            rows = {k: jnp.asarray(v)
                    for k, v in self.kv.spill.read(sid).items()}
            cache = self._offload_pool(self._promote(
                cache, jnp.int32(dst), rows, jnp.int32(keep)))
            st["spill_promotes"] += 1
        if plan.promote:
            self.telemetry.event("pool.promote", request=idx, slot=s,
                                 n=len(plan.promote))
        for sid in plan.free_spill:  # scatter dispatched: slot reusable
            self.kv.spill.free(sid)
        for src, dst in plan.cow:
            cache = self._offload_pool(self._copy(
                cache, jnp.int32(src), jnp.int32(dst),
                jnp.int32(plan.resume % self.page_size)))
            st["cow_copies"] += 1
            self.telemetry.event("pool.cow", request=idx, slot=s,
                                 src=int(src), dst=int(dst))
        # crash consistency: the radix tree now points at the promoted /
        # reset pages, so the pool holding them must survive even if this
        # workload dies before _end (a dispatch failure must not strand
        # the tree on data that only lived in the lost functional value)
        self._pool_cache = self._cur_cache = cache
        self._table_dev = None  # table changed: re-ship at next dispatch
        st["resets"] += 1
        st["prompt_tokens"] += len(prompt)
        st["prefilled_tokens"] += len(prompt) - plan.resume
        st["prefix_hit_tokens"] += plan.resume
        st["pages_peak"] = max(st["pages_peak"], self.kv.pages_in_use)
        self._inserted[s] = False
        return cache, plan.resume

    def _dispatch(self, cache, mode, tok, pos, key, rem, pfill, pend, plen):
        if self._table_dev is None:
            self._table_dev = jnp.asarray(self.kv.table)
        emits, valids, aux = self._segment(cache, mode, tok, pos, key, rem,
                                           pfill, pend, plen, self._table_dev,
                                           self._seg_params)
        aux["cache"] = self._offload_pool(aux["cache"])
        # keep the persistent pool pointing at the freshest value: pages
        # published to the radix tree mid-workload must survive a failure
        # on a LATER segment dispatch
        self._pool_cache = self._cur_cache = aux["cache"]
        return emits, valids, aux

    def _post_dispatch(self, mode, pfill, plen, pend, owner) -> None:
        for s in range(self.slots):
            if owner[s] is None or self._inserted[s] or pfill[s] < plen[s]:
                continue
            self._inserted[s] = True
            self.kv.complete_prefill(s, [int(t) for t in pend[s, : plen[s]]])

    def _release(self, s: int) -> None:
        self.kv.release(s)
        self._table_dev = None  # table changed: re-ship at next dispatch

    def _end(self, cache) -> None:
        # the pool (radix-shared prefixes included) persists across calls
        self._pool_cache = cache
        self._cur_cache = cache
        if self.kv.radix is not None:
            self.last_stats["radix_pages"] = self.kv.radix.pages
            self.last_stats["spilled_pages"] = self.kv.spilled_pages


# ---------------------------------------------------------------------------
# SLO-aware scheduling
# ---------------------------------------------------------------------------


class SLOPagedServeEngine(PagedServeEngine):
    """Priority/deadline-aware admission with spill-backed preemption over
    the paged pool.

    The compiled programs are UNTOUCHED — scheduling is pure host Python
    around the same {segment, reset, copy, promote} set, exploiting two
    properties of the substrate:

      * **Preempt = publish + release.**  A DECODE slot's cached KV covers
        the token stream ``prompt + emitted`` up to ``pos`` exactly, so
        preemption is ``complete_prefill(s, stream[:pos])`` (publish the
        full pages into the radix tree — idempotent over the already-
        published prompt prefix) followed by ``release(s)``.  The tree
        keeps the pages; under later pool pressure they demote through
        the existing :class:`SpillPool` evict path.  Resume is a plain
        re-admission of ``prompt + emitted`` with the REMAINING token
        budget: the radix match maps the cached pages back (promoting
        spilled ones through the promote scatter) and prefill restarts at
        the match boundary — the ordinary ``_admit`` resume contract.  If
        eviction dropped the pages entirely, resume re-prefills them;
        under greedy sampling the output is token-identical either way.
      * **Pause = point the row at the trash page.**  A FREE slot and a
        mid-prefill slot whose table row maps every logical page to the
        trash page are indistinguishable at the program level (the fused
        step freezes ``pos``/``pfill``/``tok`` for FREE rows and their
        dummy writes land on the trash page), so a long prefill that has
        burned its per-request chunk budget is paused by saving its table
        row, trashing it, and flipping ``mode`` to FREE — the next
        dispatch takes the pure-decode fast path, protecting co-resident
        decodes' inter-token latency.  Resume restores the row.

    Requests are :class:`repro.runtime.decode_loop.Request` (raw token
    sequences are coerced with ``priority=1``/no deadline).  ``policy``:

      ``"slo"``  — admission order ``(priority, itl_slo, arrival)``;
                   lower-priority slots (decoding OR mid-prefill — a
                   part-prefilled slot publishes ``stream[:pfill]`` and
                   resumes at the last page boundary) are preempted when
                   a strictly-higher-priority request waits; prefill-chunk
                   budgets (``Request.prefill_chunks`` or the engine-wide
                   ``prefill_budget``) pause long prefills between bursts.
      ``"fifo"`` — arrival order, no preemption, no budgets: the measured
                   baseline, byte-identical outputs to ``"slo"`` under
                   greedy sampling.

    Both policies gate admission on ``Request.arrival`` (in dispatch
    steps): a request is invisible to the scheduler before it arrives, so
    a seeded trace replays identically — goodput-under-SLO comparisons in
    ``benchmarks/serve_bench.py`` are deterministic, not wall-clock-noisy.

    Recurrent layouts (ssm/rglru) are REFUSED: preemption restores a slot
    from mapped pages, but recurrent blocks fold the whole prefix into
    per-slot state a page cannot restore (the carried ROADMAP item
    "radix reuse for recurrent layouts").
    """

    def __init__(self, cfg: ModelConfig, params: Params, *,
                 policy: str = "slo", prefill_budget: int = 0, **kw):
        if policy not in ("slo", "fifo"):
            raise ValueError(f"policy must be 'slo' or 'fifo', got "
                             f"{policy!r}")
        kw.setdefault("radix", True)
        super().__init__(cfg, params, **kw)
        if not self.radix_enabled:
            pat, _, tail = layout_of(cfg)
            kinds = sorted({k for k in (*pat, *tail) if k != "attn"})
            if kinds:
                raise ValueError(
                    f"SLOPagedServeEngine: layout contains recurrent blocks "
                    f"{kinds}; preemption resumes a request from its mapped "
                    f"KV pages, but recurrent state is integrated over the "
                    f"whole prefix and cannot be restored from a page — "
                    f"resumed output would silently diverge.  Serve this "
                    f"layout with PagedServeEngine (FIFO, no preemption); "
                    f"see the carried ROADMAP item 'radix reuse for "
                    f"recurrent layouts'")
            raise ValueError(
                "SLOPagedServeEngine requires radix=True: preempted "
                "requests resume through radix prefix matching")
        self.policy = policy
        self.prefill_budget = int(prefill_budget)

    def _capacity(self, prompts: Sequence[Sequence[int]]) -> Tuple[int, int]:
        """A preempted request re-admits ``prompt + emitted`` as its
        pending buffer, so P must cover ``longest + max_new`` (the base
        engine only needs ``longest``)."""
        longest = max((len(p) for p in prompts), default=1)
        P = -(-max(self.bucket, longest + self.max_new) // self.cp) * self.cp
        S = P + self.max_new
        if self.n_host_chunks:
            S = -(-S // self.n_host_chunks) * self.n_host_chunks
        return P, S

    def _key(self, r: DL.Request, seq: int, ridx: int) -> Tuple:
        if self.policy == "slo":
            return (r.priority, r.itl_slo, seq, ridx)
        return (seq, ridx)

    # -- the scheduler ---------------------------------------------------
    def generate(self, prompts: Sequence[Union[DL.Request, Sequence[int]]],
                 key: Optional[jnp.ndarray] = None) -> List[List[int]]:
        """Run every request to completion, honouring arrivals, priorities
        and budgets.  Returns one generated-token list per request, in
        input order (preempted requests' outputs are stitched across
        incarnations — token-identical to an uninterrupted run under
        greedy sampling).

        ``last_stats`` gains ``policy``/``preemptions``/``prefill_pauses``
        and a per-request ``requests`` list ({arrival, admit_step,
        first_emit, last_emit, max_gap, preemptions, n_emitted, priority,
        tier, prompt_len} — all step-indexed, so SLO attainment is
        deterministic given the trace)."""
        reqs = [DL.as_request(p) for p in prompts]
        self._validate([r.tokens for r in reqs])
        key = jax.random.PRNGKey(0) if key is None else key
        n = len(reqs)
        B = self.slots
        P, S = self._capacity([r.tokens for r in reqs])
        stats = self.telemetry.stats_view({
            "steps": self.telemetry.steps_ring(), "dispatches": 0,
            "resets": 0, "capacity": S,
            "pending_len": P, "policy": self.policy, "preemptions": 0,
            "prefill_pauses": 0,
            "requests": [{"arrival": int(r.arrival), "priority": r.priority,
                          "tier": r.tier, "prompt_len": len(r.tokens),
                          "admit_step": None, "first_emit": None,
                          "last_emit": None, "max_gap": 0, "preemptions": 0,
                          "n_emitted": 0} for r in reqs]})
        self.last_stats = stats
        rstat = stats["requests"]
        cache = self._begin(B, P, S)
        mode = np.full(B, DL.FREE, np.int32)
        tok = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        rem = np.zeros(B, np.int32)
        pfill = np.zeros(B, np.int32)
        pend = np.full((B, P), self.pad_id, np.int32)
        plen = np.ones(B, np.int32)
        owner: List[Optional[int]] = [None] * B
        emitted: List[List[int]] = [[] for _ in reqs]
        paused = [False] * B        # mid-prefill, parked on the trash row
        saved_rows: List[Optional[np.ndarray]] = [None] * B
        skip = [0] * B              # paused rounds left before resume
        burst = [0] * B             # prefill chunks since admit/resume
        order = sorted(range(n), key=lambda i: (reqs[i].arrival, i))
        fptr = 0
        ready: List[Tuple] = []     # heap of self._key(...) entries
        seq = n                     # requeue seqnos, past all initial ones
        step = 0                    # dispatch-step clock

        def preempt(s: int) -> None:
            ridx = owner[s]
            stream = list(reqs[ridx].tokens) + emitted[ridx]
            # KV is cached for positions [0, pos) when decoding and
            # [0, pfill) mid-prefill: publish that prefix's full pages,
            # then release — the radix tree keeps them, so re-admission
            # resumes at the last page boundary instead of restarting
            cached = int(pos[s]) if mode[s] == DL.DECODE else int(pfill[s])
            self.kv.complete_prefill(s, stream[:cached])
            self._release(s)
            owner[s] = None
            mode[s] = DL.FREE
            burst[s] = 0
            rstat[ridx]["preemptions"] += 1
            stats["preemptions"] += 1
            self.telemetry.event("request.preempt", request=ridx, slot=s,
                                 step=step, session=reqs[ridx].session,
                                 cached=cached)
            nonlocal seq
            heapq.heappush(ready, self._key(reqs[ridx], seq, ridx))
            seq += 1

        def preempt_for(head_pri: int) -> bool:
            if self.policy != "slo":
                return False
            cands = [s for s in range(B)
                     if owner[s] is not None and not paused[s]
                     and mode[s] in (DL.DECODE, DL.PREFILL)
                     and reqs[owner[s]].priority > head_pri]
            if not cands:
                return False
            preempt(max(cands, key=lambda s: (reqs[owner[s]].priority, s)))
            return True

        while True:
            # resume paused prefills (one full round parked first: the
            # intervening dispatch takes the decode fast path)
            for s in range(B):
                if not paused[s]:
                    continue
                if skip[s] > 0:
                    skip[s] -= 1
                    continue
                self.kv.table[s, :] = saved_rows[s]
                self._table_dev = None
                mode[s] = DL.PREFILL
                paused[s] = False
                burst[s] = 0
                self.telemetry.event("request.pause_resume",
                                     request=owner[s], slot=s, step=step)
            # arrivals up to the current step become schedulable
            while fptr < n and reqs[order[fptr]].arrival <= step:
                ridx = order[fptr]
                fptr += 1
                self.telemetry.event(
                    "request.queued", request=ridx,
                    session=reqs[ridx].session,
                    step=int(reqs[ridx].arrival),
                    priority=reqs[ridx].priority, tier=reqs[ridx].tier)
                heapq.heappush(ready, self._key(reqs[ridx], ridx, ridx))
            # admission: fill free slots from the ready heap, preempting
            # lower-priority decodes when the head outranks them
            progress = True
            while ready and progress:
                progress = False
                free = [s for s in range(B) if owner[s] is None]
                if not free:
                    progress = preempt_for(reqs[ready[0][-1]].priority)
                    continue
                s = free[0]
                entry = heapq.heappop(ready)
                ridx = entry[-1]
                r = reqs[ridx]
                pending = list(r.tokens) + emitted[ridx]
                budget = self.max_new - len(emitted[ridx])
                active = any(o is not None for o in owner)
                admitted = self._admit(cache, s, ridx, pending, active,
                                       budget=budget)
                if admitted is None:  # pool-exhausted: retry after preempt
                    heapq.heappush(ready, entry)
                    progress = preempt_for(r.priority)
                    continue
                cache, resume = admitted
                owner[s] = ridx
                np_ = len(pending)
                pend[s, :np_] = pending
                pend[s, np_:] = self.pad_id
                plen[s], pfill[s], mode[s] = np_, resume, DL.PREFILL
                rem[s], pos[s], tok[s] = budget, 0, self.pad_id
                burst[s] = 0
                first_admit = rstat[ridx]["admit_step"] is None
                if first_admit:
                    rstat[ridx]["admit_step"] = step
                self.telemetry.event(
                    "request.admit" if first_admit else "request.resume",
                    request=ridx, slot=s, step=step, session=r.session,
                    prompt_len=np_, prefix_hit=int(resume))
                progress = True
            if all(o is None for o in owner):
                if fptr < n:  # idle: jump the clock to the next arrival
                    step = max(step, int(reqs[order[fptr]].arrival))
                    continue
                break
            key, sub = jax.random.split(key)
            n_prefilling = int((mode == DL.PREFILL).sum())
            with TM.timed_dispatch(self.telemetry, stats,
                                   prefilling=n_prefilling, step=step) as td:
                emits, valids, aux = self._dispatch(
                    cache, mode, tok, pos, sub, rem, pfill, pend, plen)
                cache = aux["cache"]
                mode, tok, pos, rem, pfill, em, va = (
                    np.array(x) for x in jax.device_get(
                        (aux["mode"], aux["tok"], aux["pos"], aux["rem"],
                         aux["pfill"], emits, valids)))
                td.emitted = int(va.sum())
            self._post_dispatch(mode, pfill, plen, pend, owner)
            for s in range(B):
                if owner[s] is None:
                    continue
                ridx = owner[s]
                toks = [int(t) for t, v in zip(em[s], va[s]) if v]
                if toks:
                    rs = rstat[ridx]
                    if rs["first_emit"] is None:
                        rs["first_emit"] = step
                    if rs["last_emit"] is not None:
                        rs["max_gap"] = max(rs["max_gap"],
                                            step - rs["last_emit"])
                    rs["last_emit"] = step
                    emitted[ridx].extend(toks)
                    self.telemetry.event(
                        "request.emit", request=ridx, slot=s, step=step,
                        session=reqs[ridx].session, n=len(toks))
                if paused[s]:  # parked: FREE at program level, still owned
                    continue
                if mode[s] == DL.FREE:
                    self._release(s)
                    self.telemetry.event(
                        "request.complete", request=ridx, slot=s, step=step,
                        session=reqs[ridx].session, n=len(emitted[ridx]))
                    owner[s] = None
            # prefill-chunk budgets: park a long prefill so co-resident
            # decodes get a pure-decode dispatch before it continues
            if self.policy == "slo":
                any_decode = any(int(m) == DL.DECODE for m in mode)
                for s in range(B):
                    if owner[s] is None or paused[s] or mode[s] != DL.PREFILL:
                        continue
                    burst[s] += 1
                    r = reqs[owner[s]]
                    b = r.prefill_chunks or self.prefill_budget
                    if b and burst[s] >= b and any_decode:
                        saved_rows[s] = self.kv.table[s].copy()
                        self.kv.table[s, :] = self.kv.trash
                        self._table_dev = None
                        mode[s] = DL.FREE
                        paused[s] = True
                        skip[s] = 1
                        stats["prefill_pauses"] += 1
                        self.telemetry.event(
                            "request.pause", request=owner[s], slot=s,
                            step=step, session=r.session)
            step += 1
        self._end(cache)
        for i in range(n):
            rstat[i]["n_emitted"] = len(emitted[i])
        return emitted
