"""Property tests for the FPDT chunk-liveness predicate.

``pair_live`` (static, unrolled path) and ``pair_live_traced`` (jnp, scan
path) must agree everywhere, and the window semantics must equal the dense
token-level mask: a chunk pair is live iff at least one (q, k) token pair
inside it survives the causal+window band.  Runs under real hypothesis when
installed, else the deterministic fixed grid (tests/_hypothesis_compat.py).
"""
import functools
import itertools

import jax.numpy as jnp
import numpy as np

from _hypothesis_compat import given, settings, strategies as st

from repro.core.fpdt import (fwd_kv_fetches, overlapped_fetches, pair_live, pair_live_traced,
                             sparsity_stride)


def _dense_window_live(i, j, cq, window):
    """Oracle: any token pair (q in chunk i, k in chunk j) inside the band."""
    q = np.arange(i * cq, (i + 1) * cq)[:, None]
    k = np.arange(j * cq, (j + 1) * cq)[None, :]
    ok = q >= k
    if window:
        ok = ok & (q - k < window)
    return bool(ok.any())


@settings(max_examples=60, deadline=None)
@given(u=st.integers(min_value=1, max_value=8),
       cq=st.sampled_from([1, 4, 8]),
       window=st.sampled_from([0, 1, 5, 8, 17]),
       sparsity=st.sampled_from([0.0, 0.3, 0.5, 0.75, 0.9]))
def test_traced_matches_static(u, cq, window, sparsity):
    kw = dict(cq=cq, window=window, sparsity=sparsity)
    for i, j in itertools.product(range(u), repeat=2):
        static = pair_live(i, j, **kw)
        traced = bool(pair_live_traced(jnp.int32(i), jnp.int32(j), **kw))
        assert static == traced, (i, j, kw)


@settings(max_examples=40, deadline=None)
@given(u=st.integers(min_value=1, max_value=8),
       cq=st.sampled_from([1, 4, 8]),
       window=st.sampled_from([0, 1, 5, 8, 17]))
def test_window_equals_dense_mask(u, cq, window):
    """With sparsity off, chunk liveness == OR-reduction of the token mask."""
    for i, j in itertools.product(range(u), repeat=2):
        assert pair_live(i, j, cq=cq, window=window, sparsity=0.0) == \
            _dense_window_live(i, j, cq, window), (i, j, cq, window)


@settings(max_examples=40, deadline=None)
@given(u=st.integers(min_value=2, max_value=8),
       cq=st.sampled_from([4, 8]),
       sparsity=st.sampled_from([0.3, 0.5, 0.75, 0.9]))
def test_sparsity_invariants(u, cq, sparsity):
    kw = dict(cq=cq, window=0, sparsity=sparsity)
    stride = sparsity_stride(sparsity)
    for i in range(u):
        # the diagonal is always attended (exactness of the local softmax)
        assert pair_live(i, i, **kw)
        # future chunks never
        for j in range(i + 1, u):
            assert not pair_live(i, j, **kw)
        # off-diagonal keep-set is exactly the distance-stride comb
        for j in range(i):
            assert pair_live(i, j, **kw) == ((i - j - 1) % stride == 0)


def test_dense_schedule_keeps_everything():
    for u, cq in [(1, 8), (4, 4), (8, 2)]:
        for i, j in itertools.product(range(u), repeat=2):
            assert pair_live(i, j, cq=cq, window=0, sparsity=0.0) == (j <= i)


def _one_chunk_fetches(u, cq, window, sparsity):
    """Fetches of a forward that takes one query chunk per step: every live
    off-diagonal pair fetches its KV chunk."""
    return sum(pair_live(i, j, cq=cq, window=window, sparsity=sparsity)
               for i in range(u) for j in range(i))


def test_fwd_kv_fetches_dense():
    """u=16 (the 64k cell): 56 fetches a layer, against 120 for one query
    chunk per step; any even u fetches u(u-2)/4, an odd u u(u-1)/2."""
    assert fwd_kv_fetches(16, cq=4096) == 56
    assert _one_chunk_fetches(16, 4096, 0, 0.0) == 120
    for u in (2, 4, 6, 8, 32):
        assert fwd_kv_fetches(u, cq=8) == u * (u - 2) // 4
    for u in (1, 3, 5, 7, 9, 15):
        assert fwd_kv_fetches(u, cq=8) == u * (u - 1) // 2 == _one_chunk_fetches(u, 8, 0, 0.0)


def test_grouped_fetches_never_exceed_one_chunk():
    """Under every window and sparsity the tests use, the grouped forward
    fetches no more KV chunks than one query chunk per step does."""
    for u, cq, window, sparsity in itertools.product(
            range(2, 17), (4, 8), (0, 1, 5, 9, 12), (0.0, 0.5, 0.67)):
        assert fwd_kv_fetches(u, cq=cq, window=window, sparsity=sparsity) <= \
            _one_chunk_fetches(u, cq, window, sparsity), (u, cq, window, sparsity)


def test_overlapped_fetches():
    """Dense u=16 (the 64k cell): 49 of the forward's 56 KV fetches a pass and
    120 of Fig. 7's 136 q fetches are issued while a chunk pair's kernels
    run, every fetch but each loop's first; u=4: 1 of 2 and 6 of 10.  A
    window or sparsity schedule, u=1, one head group and no offload take no
    such path."""
    over = functools.partial(overlapped_fetches, head_groups=2, offload=True)
    assert over(16, cq=4096) == (49, 120)
    assert sum(16 - j for j in range(16)) == 136
    assert over(4, cq=8) == (1, 6)
    for u in range(2, 17):
        fwd, bwd = over(u, cq=8)
        loops = len([i0 for i0 in range(0, u, 2 if u % 2 == 0 else 1) if i0])
        assert fwd == fwd_kv_fetches(u, cq=8) - loops
        assert bwd == sum(u - j for j in range(u)) - u
    assert over(1, cq=8) == (0, 0)
    assert over(16, cq=4096, window=4096) == (0, 0)
    assert over(16, cq=4096, sparsity=0.5) == (0, 0)
    assert overlapped_fetches(16, cq=4096, head_groups=1, offload=True) == (0, 0)
    assert overlapped_fetches(16, cq=4096, head_groups=2, offload=False) == (0, 0)
