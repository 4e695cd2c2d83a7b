"""Paged KV pool (`runtime/paged.py` + the paged attention twins in
`models/serve.py`): allocator/refcount/COW invariants (property tests),
radix prefix reuse, paged == dense engine parity (logits and harvested
ids) across layouts, prefix-reuse == full-recompute, pool-exhaustion
hardening, and (slow) program-size flatness in ``n_pages``."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.models import serve as SV
from repro.models import transformer as T
from repro.runtime import decode_loop as DL
from repro.runtime import paged as PG


@functools.lru_cache(maxsize=4)
def setup(name):
    cfg = dataclasses.replace(reduced(get_config(name)), param_dtype="float32",
                              remat="none")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def prompts_for(cfg, lens, seed=0, prefix=()):
    rng = np.random.default_rng(seed)
    return [list(prefix) + rng.integers(0, cfg.vocab_size, size=n).tolist()
            for n in lens]


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------


@settings(max_examples=40)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_pages=st.integers(min_value=1, max_value=17))
def test_pool_allocator_invariants(seed, n_pages):
    """Random alloc/share/release traces against a reference model: the
    free list never double-allocates, a page is free iff refcount == 0,
    and exhaustion raises instead of handing out a live page."""
    rng = np.random.default_rng(seed)
    pool = PG.PagePool(n_pages)
    live = {}  # pid -> reference refcount
    for _ in range(60):
        op = rng.integers(0, 3)
        if op == 0:  # alloc
            if len(live) == n_pages:
                with pytest.raises(PG.PoolExhausted):
                    pool.alloc()
            else:
                pid = pool.alloc()
                assert pid not in live, "double allocation"
                live[pid] = 1
        elif op == 1 and live:  # share
            pid = int(rng.choice(list(live)))
            pool.share(pid)
            live[pid] += 1
        elif op == 2 and live:  # release
            pid = int(rng.choice(list(live)))
            pool.release(pid)
            live[pid] -= 1
            if live[pid] == 0:
                del live[pid]
        assert pool.used_count == len(live)
        assert pool.free_count == n_pages - len(live)
        for pid, rc in live.items():
            assert int(pool.refcount[pid]) == rc
    for pid in range(n_pages):  # dead pages really are at refcount 0
        assert (pid in live) == (int(pool.refcount[pid]) > 0)


def test_pool_misuse_raises():
    pool = PG.PagePool(2)
    a = pool.alloc()
    pool.release(a)
    with pytest.raises(ValueError):
        pool.release(a)
    with pytest.raises(ValueError):
        pool.share(a)


# ---------------------------------------------------------------------------
# radix tree
# ---------------------------------------------------------------------------


def test_radix_match_insert_evict():
    pool = PG.PagePool(16)
    tree = PG.RadixTree(4, pool)
    toks = list(range(10))  # 2 full pages + partial
    pids = [pool.alloc(), pool.alloc()]
    assert tree.insert(toks, pids) == 2
    assert tree.pages == 2 and int(pool.refcount[pids[0]]) == 2
    # full match; partial page never matched
    assert tree.match(toks) == pids
    assert tree.match(toks[:7]) == pids[:1]
    assert tree.match([99] + toks[1:]) == []
    # re-insert of the same prefix adds nothing (first prefill wins)
    assert tree.insert(toks, [pool.alloc(), pool.alloc()]) == 0
    # owner releases; tree keeps the pages alive
    for pid in pids:
        pool.release(pid)
    assert int(pool.refcount[pids[0]]) == 1
    # eviction is leaf-first and only touches tree-only pages
    pool.share(pids[1])  # someone still maps the leaf
    assert tree.evict(2) == 0  # leaf pinned -> its prefix chain survives too
    pool.release(pids[1])
    assert tree.evict(2) == 2 and tree.pages == 0
    assert int(pool.refcount[pids[0]]) == 0 and int(pool.refcount[pids[1]]) == 0


def test_radix_lru_eviction_order():
    pool = PG.PagePool(8)
    tree = PG.RadixTree(2, pool)
    old = [pool.alloc()]
    new = [pool.alloc()]
    tree.insert([1, 2], old)
    tree.insert([3, 4], new)
    tree.match([3, 4])  # freshen the second branch
    for p in (*old, *new):
        pool.release(p)
    assert tree.evict(1) == 1
    assert int(pool.refcount[old[0]]) == 0  # LRU went first
    assert int(pool.refcount[new[0]]) == 1


# ---------------------------------------------------------------------------
# page tables + COW
# ---------------------------------------------------------------------------


@settings(max_examples=20)
@given(ps=st.sampled_from([1, 2, 4]),
       plen=st.integers(min_value=1, max_value=24),
       budget=st.integers(min_value=0, max_value=9))
def test_manager_reserve_and_release(ps, plen, budget):
    """Admission maps exactly the worst-case reserve, the rest of the row
    is unmapped, and release returns every page."""
    mgr = PG.PagedCacheManager(64, ps, use_radix=False)
    mgr.begin(2, max_pages=-(-(24 + budget) // ps))
    toks = list(range(plen))
    plan = mgr.admit(0, toks, budget)
    need = max(-(-(plen + budget) // ps), 1)
    assert plan.resume == 0 and plan.cow == [] and len(plan.fresh_pages) == need
    row = mgr.table[0]
    assert (row[:need] >= 0).all() and (row[need:] == -1).all()
    assert len(set(row[:need].tolist())) == need  # all distinct
    assert mgr.pages_in_use == need
    mgr.release(0)
    assert mgr.pages_in_use == 0
    assert (mgr.table[0] == mgr.trash).all()


@settings(max_examples=20)
@given(seed=st.integers(min_value=0, max_value=1000),
       ps=st.sampled_from([2, 4]))
def test_cow_divergence_isolates_tables(seed, ps):
    """After ``ensure_writable`` no page is reachable from two tables:
    the diverged page is exclusively owned, refcounts stay exact, and
    still-shared prefix pages keep their sharers."""
    rng = np.random.default_rng(seed)
    mgr = PG.PagedCacheManager(32, ps, use_radix=True)
    mgr.begin(2, max_pages=8)
    toks = rng.integers(0, 100, size=3 * ps).tolist()  # 3 full pages
    p0 = mgr.admit(0, toks, 0)
    mgr.complete_prefill(0, toks)
    p1 = mgr.admit(1, toks, 0)  # full-cover match -> COW of the last page
    assert p1.hit_pages == 3 and p1.resume == 3 * ps - 1
    assert len(p1.cow) == 1
    src, dst = p1.cow[0]
    assert src == mgr.table[0, 2] and dst == mgr.table[1, 2] and src != dst
    # shared prefix pages appear in both tables; the diverged page in one
    shared = set(mgr.table[0, :2].tolist()) & set(mgr.table[1, :2].tolist())
    assert len(shared) == 2
    assert int(mgr.pool.refcount[dst]) == 1
    # a forced write to a still-shared page also diverges it
    pair = mgr.ensure_writable(1, 0)
    assert pair is not None and mgr.table[1, 0] != mgr.table[0, 0]
    assert mgr.ensure_writable(1, 0) is None  # already exclusive
    both = set(mgr.table[0].tolist()) & set(mgr.table[1].tolist()) - {-1}
    for pid in both:  # anything still common is genuinely shared (rc > 1)
        assert int(mgr.pool.refcount[pid]) > 1
    mgr.release(0)
    mgr.release(1)
    assert mgr.pages_in_use == mgr.radix.pages  # only the tree's refs left


def test_cow_source_survives_admit_eviction():
    """A full-cover admit under pool pressure must not evict the page its
    own COW copy reads from: eviction makes room out of OTHER tree leaves
    and the (src, dst) pair stays a real copy, never src == dst."""
    ps = 4
    mgr = PG.PagedCacheManager(6, ps, use_radix=True)
    mgr.begin(1, max_pages=6)
    a, b = list(range(2 * ps)), list(range(100, 100 + 2 * ps))
    for toks in (a, b):
        mgr.admit(0, toks, 0)
        mgr.complete_prefill(0, toks)
        mgr.release(0)
    assert mgr.pages_in_use == 4  # both prompts live only in the tree
    a_pages = mgr.radix.match(a)
    plan = mgr.admit(0, a, 2 * ps)  # need 4: forces eviction of b's leaf
    assert plan.cow and plan.cow[0][0] == a_pages[1]
    src, dst = plan.cow[0]
    assert src != dst
    assert int(mgr.pool.refcount[src]) >= 1  # still alive (tree's ref)
    assert len(mgr.radix.match(b)) == 1  # b's LEAF page paid for the room


def test_begin_recovers_aborted_workload():
    """An exception mid-generate leaves slots admitted; the next workload's
    begin() releases them instead of wedging the engine for good."""
    mgr = PG.PagedCacheManager(8, 4, use_radix=False)
    mgr.begin(2, max_pages=4)
    mgr.admit(0, [1, 2, 3], 4)
    assert mgr.pages_in_use > 0
    mgr.begin(2, max_pages=4)  # no raise; leaked pages returned
    assert mgr.pages_in_use == 0


def test_manager_exhaustion_and_eviction():
    ps = 4
    mgr = PG.PagedCacheManager(4, ps, use_radix=True)
    mgr.begin(2, max_pages=4)
    toks = list(range(2 * ps))
    mgr.admit(0, toks, 0)
    mgr.complete_prefill(0, toks)
    with pytest.raises(PG.PoolExhausted, match="request 9"):
        mgr.admit(1, list(range(100, 100 + 3 * ps)), 0, label="request 9")
    mgr.release(0)  # tree still holds the 2 full pages
    assert mgr.pages_in_use == 2
    # the next admission evicts the tree's pages to make room
    mgr.admit(1, list(range(100, 100 + 3 * ps)), ps)
    assert mgr.pages_in_use == 4 and mgr.radix.pages == 0


# ---------------------------------------------------------------------------
# paged == dense parity
# ---------------------------------------------------------------------------


def test_chunk_step_paged_logit_parity():
    """Direct step parity: chunked prefill + one decode step through the
    page pool == the same through the dense cache (logits and the decode
    step's sampled-from logits), at page sizes that straddle the chunk."""
    cfg, params = setup("llama3.2-1b")
    rng = np.random.default_rng(3)
    b, cp = 2, 4
    lens = [9, 6]
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(b, 9)), jnp.int32)
    for ps in (2, 8):  # page < chunk and page > chunk
        dense = SV.init_cache(cfg, b, 16)
        mgr = PG.PagedCacheManager(16, ps, use_radix=False)
        mgr.begin(b, max_pages=-(-16 // ps))
        for s, n in enumerate(lens):
            mgr.admit(s, [int(t) for t in toks[s, :n]], 16 - n)
        paged = SV.init_paged_cache(cfg, b, 16, ps)
        table = jnp.asarray(mgr.table)
        pfill = np.zeros(b, np.int32)
        plen = np.asarray(lens, np.int32)
        while (pfill < plen).any():
            live = np.clip(plen - pfill, 0, cp)
            idx = np.clip(pfill[:, None] + np.arange(cp)[None], 0, 8)
            chunk = jnp.asarray(np.asarray(toks)[np.arange(b)[:, None], idx])
            ld, dense = SV.chunk_step(cfg, None, params, dense, chunk,
                                      jnp.asarray(pfill), jnp.asarray(live))
            lp, paged = SV.chunk_step(cfg, None, params, paged, chunk,
                                      jnp.asarray(pfill), jnp.asarray(live),
                                      table=table)
            np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                       rtol=1e-5, atol=1e-5)
            pfill += live
        nxt = jnp.argmax(ld[:, : cfg.vocab_size], -1).astype(jnp.int32)[:, None]
        ld2, _ = SV.decode_step(cfg, None, params, dense, {"tokens": nxt},
                                jnp.asarray(plen))
        lp2, _ = SV.decode_step(cfg, None, params, paged, {"tokens": nxt},
                                jnp.asarray(plen), table=table)
        np.testing.assert_allclose(np.asarray(lp2), np.asarray(ld2),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["llama3.2-1b", "falcon-mamba-7b",
                                  "recurrentgemma-9b"])
def test_paged_engine_matches_dense(name):
    """The staggered mixed-length workload (queue > slots, prompts longer
    than the bucket, stop-token finishes) harvests identical ids from the
    paged and dense engines; the paged pool stays within its page bound."""
    cfg, params = setup(name)
    prompts = prompts_for(cfg, (3, 8, 5, 12, 6), seed=0)
    kw = dict(slots=2, bucket=8, max_new_tokens=5, segment=2, prefill_chunk=4)
    ref = DL.ServeEngine(cfg, params, **kw).generate(prompts)
    stop = ref[0][2]

    def trunc(g):
        return g[: g.index(stop) + 1] if stop in g else g

    ref = [trunc(g) for g in ref]
    eng = PG.PagedServeEngine(cfg, params, page_size=4, stop_tokens=(stop,),
                              **kw)
    ref_eng = DL.ServeEngine(cfg, params, stop_tokens=(stop,), **kw)
    assert eng.generate(prompts) == ref_eng.generate(prompts) == ref
    st = eng.last_stats
    assert st["pages_peak"] <= eng.n_pages
    assert eng.compiled_programs()["segment"] == 1


def test_paged_engine_host_streamed():
    """n_host_chunks > 0: the pool's K/V stay in the offload pool between
    AND inside programs, pages stream through fori_double_buffered and
    writes are whole-page read-modify-writes — same ids as dense."""
    from repro.core.parallel import ParallelContext

    cfg, params = setup("llama3.2-1b")
    prompts = prompts_for(cfg, (5, 9, 3), seed=4)
    kw = dict(slots=2, bucket=8, max_new_tokens=4, segment=2, prefill_chunk=4)
    ref = DL.ServeEngine(cfg, params, **kw).generate(prompts)
    par = ParallelContext(mesh=None)
    eng = PG.PagedServeEngine(cfg, params, page_size=4, n_host_chunks=2,
                              par=par, **kw)
    assert eng.generate(prompts) == ref
    assert eng.compiled_programs()["segment"] == eng.compiled_programs()["reset"] == 1
    if par.offload_active:
        kinds = {str(path[-1].key): leaf.sharding.memory_kind for path, leaf
                 in jax.tree_util.tree_flatten_with_path(eng._pool_cache)[0]}
        assert kinds["pk"] == kinds["pv"] == par.pol.host_kind
        assert kinds["pkpos"] != par.pol.host_kind


# ---------------------------------------------------------------------------
# prefix reuse
# ---------------------------------------------------------------------------


def test_prefix_reuse_matches_full_recompute():
    """Requests sharing a long prefix: radix-on output == radix-off output
    == dense output, prefilled-token count drops by the pages actually
    shared, and peak pool usage undercuts the dense-equivalent cache."""
    cfg, params = setup("llama3.2-1b")
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, size=16).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in (3, 5, 7, 2, 6, 4)]
    # bucket > longest prompt: the dense cache pays slots x bucket rows
    # regardless; the pool only pays pages actually reserved
    kw = dict(slots=2, bucket=32, max_new_tokens=4, segment=2, prefill_chunk=4)
    ref = DL.ServeEngine(cfg, params, **kw).generate(prompts)
    off = PG.PagedServeEngine(cfg, params, page_size=4, n_pages=32,
                              radix=False, **kw)
    on = PG.PagedServeEngine(cfg, params, page_size=4, n_pages=32, **kw)
    assert off.generate(prompts) == ref
    assert on.generate(prompts) == ref
    st_off, st_on = off.last_stats, on.last_stats
    assert st_off["prefix_hit_tokens"] == 0
    # every request after the first finished prefill maps the 4 shared pages
    assert st_on["prefix_hit_tokens"] >= 16 * (len(prompts) - 2)
    assert (st_on["prefilled_tokens"]
            == st_on["prompt_tokens"] - st_on["prefix_hit_tokens"])
    # dense equivalent: slots x ceil(capacity / ps) pages
    dense_pages = kw["slots"] * -(-st_on["capacity"] // 4)
    assert st_on["pages_peak"] < dense_pages
    # the prefix survives for the NEXT workload too (pool persists)
    on.generate(prompts[:2])
    assert on.last_stats["prefix_hit_tokens"] >= 16


def test_engine_cow_on_identical_prompts():
    """Identical prompts with plen % page_size == 0: the radix match covers
    the whole prompt, so the resumed last-token prefill COWs the final
    page — output still equals the dense engine's."""
    cfg, params = setup("llama3.2-1b")
    prompt = prompts_for(cfg, (16,), seed=8)[0]
    prompts = [prompt, prompt, prompt]
    kw = dict(slots=2, bucket=16, max_new_tokens=4, segment=2, prefill_chunk=4)
    ref = DL.ServeEngine(cfg, params, **kw).generate(prompts)
    eng = PG.PagedServeEngine(cfg, params, page_size=4, n_pages=32, **kw)
    assert eng.generate(prompts) == ref
    st = eng.last_stats
    assert st["cow_copies"] >= 1
    assert st["prefix_hit_tokens"] >= 15  # plen - 1 per full-cover hit


# ---------------------------------------------------------------------------
# hardening
# ---------------------------------------------------------------------------


def test_paged_validation_errors():
    cfg, params = setup("llama3.2-1b")
    kw = dict(slots=2, bucket=8, max_new_tokens=4, segment=2)
    with pytest.raises(ValueError, match="prefill_chunk=6 and page_size=4"):
        PG.PagedServeEngine(cfg, params, prefill_chunk=6, page_size=4, **kw)
    with pytest.raises(ValueError, match="page_size must be >= 1"):
        PG.PagedServeEngine(cfg, params, prefill_chunk=4, page_size=0, **kw)
    # a request that could NEVER fit names itself instead of tracing
    eng = PG.PagedServeEngine(cfg, params, prefill_chunk=4, page_size=4,
                              n_pages=2, **kw)
    with pytest.raises(ValueError, match="request 1"):
        eng.generate([[1, 2, 3], [4] * 32])


def test_pool_pressure_defers_not_fails():
    """A pool sized for one request at a time still drains a multi-request
    queue: admission defers while other slots hold pages, and the output
    equals the roomy engine's."""
    cfg, params = setup("llama3.2-1b")
    prompts = prompts_for(cfg, (7, 6, 8), seed=9)
    kw = dict(slots=2, bucket=8, max_new_tokens=4, segment=2, prefill_chunk=4)
    ref = DL.ServeEngine(cfg, params, **kw).generate(prompts)
    eng = PG.PagedServeEngine(cfg, params, page_size=4, n_pages=3,
                              radix=False, **kw)
    assert eng.generate(prompts) == ref
    assert eng.last_stats["deferrals"] > 0


# ---------------------------------------------------------------------------
# spill tier + persistence
# ---------------------------------------------------------------------------


def _rescan_evict_order(nodes, n_pages):
    """Reference model of the RETIRED O(pages^2) eviction: re-collect every
    evictable leaf per freed page, take the min stamp.  ``nodes`` is a
    plain mirror [{page, last_used, parent_idx, alive}]; returns the page
    ids in eviction order (no spill tier: every victim is dropped)."""
    order = []
    while len(order) < n_pages:
        children = {}
        for i, nd in enumerate(nodes):
            if nd["alive"] and nd["parent"] >= 0:
                children.setdefault(nd["parent"], []).append(i)
        cand = [i for i, nd in enumerate(nodes)
                if nd["alive"] and not any(nodes[c]["alive"]
                                           for c in children.get(i, []))]
        if not cand:
            break
        victim = min(cand, key=lambda i: nodes[i]["last_used"])
        nodes[victim]["alive"] = False
        order.append(nodes[victim]["page"])
    return order


@settings(max_examples=25)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_evict=st.integers(min_value=1, max_value=12))
def test_evict_single_pass_matches_rescan_order(seed, n_evict):
    """Property: the single-pass heap eviction frees exactly the pages the
    retired rescan-per-page algorithm would, in the same order."""
    rng = np.random.default_rng(seed)
    pool = PG.PagePool(24)
    tree = PG.RadixTree(1, pool)
    # random forest: chains off random prefixes, then randomized LRU stamps
    seqs = [rng.integers(0, 4, size=rng.integers(1, 5)).tolist()
            for _ in range(rng.integers(2, 7))]
    for s in seqs:
        have = len(tree.match(s))
        pids = [pool.alloc() for _ in range(len(s) - have)]
        tree.insert(s, tree.match(s)[:have] + pids)
        for p in pids:
            pool.release(p)
    for s in rng.permutation(len(seqs)):
        tree.match(seqs[s])  # scramble recency
    # mirror the live tree into the plain reference structure
    mirror, idx_of = [], {}
    stack = [(tree.root, -1)]
    while stack:
        nd, pidx = stack.pop()
        if nd is not tree.root:
            idx_of[id(nd)] = len(mirror)
            mirror.append({"page": nd.page, "last_used": nd.last_used,
                           "parent": pidx, "alive": True})
        me = idx_of.get(id(nd), -1)
        stack.extend((c, me) for c in nd.children.values())
    want = _rescan_evict_order(mirror, n_evict)
    got = []
    orig = pool.release
    pool.release = lambda pid: (got.append(pid), orig(pid))[1]
    try:
        freed = tree.evict(n_evict)
    finally:
        pool.release = orig
    assert got == want and freed == len(want)


def test_spill_pool_and_radix_demotion():
    """Radix-level tier mechanics: eviction demotes payloads host-side
    through read_page, spilled nodes match (as -1) without dying, insert
    re-points a spilled twin at a fresh device page, and a full tier
    degrades to dropping leaves — never a node with spilled children."""
    pool = PG.PagePool(8)
    spill = PG.SpillPool(2)
    tree = PG.RadixTree(2, pool, spill=spill)
    reads = []
    tree.read_page = lambda pid: (reads.append(pid),
                                  {"pk": np.full(3, pid, np.float32)})[1]
    chains = {"a": [1, 2, 3, 4], "b": [5, 6], "c": [7, 8]}
    pids = {}
    for k, toks in chains.items():
        ps_ = [pool.alloc() for _ in range(len(toks) // 2)]
        tree.insert(toks, ps_)
        pids[k] = ps_
        for p in ps_:
            pool.release(p)
    tree.match(chains["a"])  # a is freshest; b, c are LRU
    assert tree.evict(2) == 2  # demotes b's and c's leaves
    assert sorted(reads) == sorted([pids["b"][0], pids["c"][0]])
    assert tree.spilled == 2 and tree.pages == 2
    assert tree.match(chains["b"]) == [-1]  # spilled, still matchable
    assert np.all(spill.read(tree.match_nodes(chains["b"])[0].spill)["pk"]
                  == pids["b"][0])
    # tier is full: next eviction DROPS the leaf, keeps spilled-child parents
    assert tree.evict(2) == 2  # a's chain: leaf dropped, then its parent
    assert tree.pages == 0 and tree.match(chains["a"]) == []
    # re-prefill of b: the spilled twin is re-pointed, host copy freed
    fresh = pool.alloc()
    assert tree.insert(chains["b"], [fresh]) == 1
    assert tree.match(chains["b"]) == [fresh] and tree.spilled == 1
    pool.release(fresh)
    # misuse raises
    with pytest.raises(ValueError, match="n_spill"):
        PG.SpillPool(0)
    sid = spill.alloc()
    spill.free(sid)
    with pytest.raises(ValueError, match="unallocated"):
        spill.free(sid)


def test_spill_demote_promote_engine_parity():
    """End-to-end tier round-trip: a tight pool demotes the radix pages of
    workload A while B runs, re-serving A promotes them back — outputs
    stay identical to the dense engine across all three workloads, and the
    compiled set stays {segment, reset, copy, promote}, each <= 1."""
    cfg, params = setup("llama3.2-1b")
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, size=16).tolist()
    wl_a = [shared + rng.integers(0, cfg.vocab_size, size=k).tolist()
            for k in (3, 5)]
    wl_b = [rng.integers(0, cfg.vocab_size, size=20).tolist()
            for _ in range(3)]
    kw = dict(slots=2, bucket=24, max_new_tokens=4, segment=2,
              prefill_chunk=4)
    dense = DL.ServeEngine(cfg, params, **kw)
    ref = [dense.generate(w) for w in (wl_a, wl_b, wl_a)]
    eng = PG.PagedServeEngine(cfg, params, page_size=4, n_pages=16,
                              spill_pages=32, **kw)
    got = [eng.generate(w) for w in (wl_a, wl_b, wl_a)]
    assert got == ref
    st = eng.last_stats
    assert st["spill_promotes"] > 0, st  # pages came back from the tier
    assert st["prefix_hit_tokens"] >= 16, st
    progs = eng.compiled_programs()
    assert set(progs) == {"segment", "reset", "copy", "promote"}
    assert all(v <= 1 for v in progs.values()), progs
    assert progs["promote"] == 1, progs


def test_kv_store_save_restore_roundtrip(tmp_path):
    """Persistence: a fresh engine restored from ``save_kv_store`` serves
    the saved prefixes as radix hits (promoted from the spill tier) with
    outputs identical to the engine that built the cache, and restore
    validates page_size / spill-tier preconditions."""
    cfg, params = setup("llama3.2-1b")
    rng = np.random.default_rng(13)
    shared = rng.integers(0, cfg.vocab_size, size=16).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in (3, 6)]
    kw = dict(slots=2, bucket=24, max_new_tokens=4, segment=2,
              prefill_chunk=4, page_size=4, n_pages=16)
    eng = PG.PagedServeEngine(cfg, params, spill_pages=8, **kw)
    want = eng.generate(prompts)
    store = str(tmp_path / "kv.npz")
    saved = eng.save_kv_store(store)
    assert saved == eng.kv.radix.pages + eng.kv.spilled_pages > 0
    eng2 = PG.PagedServeEngine(cfg, params, spill_pages=32, **kw)
    assert eng2.restore_kv_store(store) == saved
    assert eng2.kv.spilled_pages == saved  # restored pages start host-side
    got = eng2.generate(prompts)
    assert got == want
    st = eng2.last_stats
    assert st["prefix_hit_tokens"] >= 16, st  # the shared prefix radix-hit
    assert st["spill_promotes"] > 0, st
    assert eng2.compiled_programs()["promote"] == 1
    # validation: a mismatched pool geometry must refuse loudly
    with pytest.raises(ValueError, match="page_size"):
        PG.PagedServeEngine(cfg, params, spill_pages=8,
                            **dict(kw, page_size=8,
                                   prefill_chunk=8)).restore_kv_store(store)
    with pytest.raises(ValueError, match="spill"):
        PG.PagedServeEngine(cfg, params, **kw).restore_kv_store(store)
    # save with live device pages needs the engine's page reader: the raw
    # manager without one refuses rather than writing garbage
    bare = PG.PagedCacheManager(8, 4)
    bare.begin(1, 4)
    bare.admit(0, list(range(8)), 0)
    bare.complete_prefill(0, list(range(8)))
    with pytest.raises(ValueError, match="read_page"):
        bare.save(str(tmp_path / "bare.npz"))
    # extension dtypes (bfloat16 pools) survive the npz round-trip: npz
    # would otherwise store them as opaque void and restore would crash
    import ml_dtypes
    pool = PG.PagePool(4)
    tree = PG.RadixTree(2, pool, spill=PG.SpillPool(4))
    pid = pool.alloc()
    tree.insert([9, 9], [pid])
    pool.release(pid)
    payload = {"pk": np.arange(6, dtype=ml_dtypes.bfloat16).reshape(2, 3)}
    bf_store = str(tmp_path / "bf16.npz")
    tree.save(bf_store, lambda _pid: payload)
    tree2 = PG.RadixTree(2, PG.PagePool(4), spill=PG.SpillPool(4))
    assert tree2.restore(bf_store) == 1
    got = tree2.spill.read(tree2.match_nodes([9, 9])[0].spill)
    assert got["pk"].dtype == payload["pk"].dtype
    assert np.array_equal(got["pk"], payload["pk"])


def test_dispatch_failure_releases_slots_spill_survives(monkeypatch):
    """Satellite: a dispatch exception mid-generate leaves slots admitted;
    the next workload's begin() releases their pages while radix-indexed
    AND spilled pages survive — the engine un-wedges without losing the
    prefix cache."""
    cfg, params = setup("llama3.2-1b")
    rng = np.random.default_rng(17)
    shared = rng.integers(0, cfg.vocab_size, size=16).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in (3, 5)]
    evictors = [rng.integers(0, cfg.vocab_size, size=20).tolist()
                for _ in range(3)]
    kw = dict(slots=2, bucket=24, max_new_tokens=4, segment=2,
              prefill_chunk=4)
    ref_eng = DL.ServeEngine(cfg, params, **kw)
    ref = [ref_eng.generate(w) for w in (prompts, evictors, prompts)]
    eng = PG.PagedServeEngine(cfg, params, page_size=4, n_pages=16,
                              spill_pages=32, **kw)
    assert eng.generate(prompts) == ref[0]
    assert eng.generate(evictors) == ref[1]  # pressure demotes A's prefix
    spilled = eng.kv.spilled_pages
    radix = eng.kv.radix.pages
    assert spilled > 0
    orig = PG.PagedServeEngine._dispatch

    def boom(self, *a, **k):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(PG.PagedServeEngine, "_dispatch", boom)
    with pytest.raises(RuntimeError, match="injected"):
        eng.generate(prompts)
    # slots stayed admitted (the failure skipped release)
    assert any(eng.kv._slot_pages), "failure should leave admitted slots"
    monkeypatch.setattr(PG.PagedServeEngine, "_dispatch", orig)
    out = eng.generate(prompts)  # begin() releases the wedged slots
    assert out == ref[2]
    st = eng.last_stats
    assert st["prefix_hit_tokens"] >= 16, st  # prefix cache survived
    assert radix + spilled >= 1  # sanity on the pre-failure snapshot
    # no tier-slot leak: every used spill slot is owned by exactly one
    # live tree node (the failed workload's promotes freed their slots)
    owners = []
    stack = [eng.kv.radix.root]
    while stack:
        nd = stack.pop()
        owners.extend(c.spill for c in nd.children.values() if c.spill >= 0)
        stack.extend(nd.children.values())
    assert sorted(owners) == sorted(set(owners))
    assert len(owners) == eng.kv.spilled_pages


# ---------------------------------------------------------------------------
# program-size / acceptance (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_paged_program_flat_in_n_pages():
    """Acceptance bar: the paged mixed-step program neither grows nor
    multiplies from n_pages 32 -> 512, and the engine's compiled-program
    set does not grow on a re-run."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import serve_bench as SB

    small, big = (SB.measure_paged(n, 8) for n in (32, 512))
    assert big["jaxpr_eqns"] <= small["jaxpr_eqns"]
    assert big["hlo_ops"] <= 1.01 * small["hlo_ops"]

    r = SB.shared_prefix_workload(prefix_len=1024, requests=8)
    assert r["programs"] == r["programs_before"], r
    assert r["programs"]["segment"] == 1
    # prefilled tokens drop by the shared fraction (every request past the
    # pipelined first wave skips the full prefix pages)
    assert r["hit_rate"] > 0.5, r
    assert r["pages_peak"] < r["dense_equiv_pages"], r
