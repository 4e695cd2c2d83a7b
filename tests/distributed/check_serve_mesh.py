"""Sharded serve-engine parity on a forced multi-device host platform.

Spawned by tests/test_serve_mesh.py (the main pytest process keeps a single
visible device).  Builds the (2 data, 4 model) serve mesh out of 8 fake CPU
devices and asserts that a mesh-sharded ``PagedServeEngine`` — paged pool
kv-heads over ``model`` per ``cache_shardings``, segment jit carrying
``in_shardings``/``out_shardings`` — reproduces the single-device engine:

  * llama hkv=4: heads divide the model axis -> head-sharded pool; direct
    ``chunk_step``/``decode_step`` logits parity at 1e-5 AND engine token
    parity, including a SECOND generate that must land as a radix prefix
    hit on both engines;
  * llama hkv=2: heads do NOT divide sp=4 -> in-page sequence fallback
    (page_size % 4 == 0), token parity;
  * ssm (falcon-mamba): recurrent per-slot state on the mesh, token
    parity (paged pool degrades to per-slot dense state there);
  * dense (non-paged) ServeEngine on the mesh, token parity;
  * llama ps=4 + host streaming: the pool's K/V stay host-side with
    their page rows (kv_heads * head_dim wide) split over all 8 devices,
    though page_size does not divide the mesh;
  * llama-spill: a tight pool demotes radix pages to the spill tier and
    re-serves them through the sharded promote scatter, matching the
    single-device oracle across the whole three-workload sequence.

Every engine must still report exactly its bounded program set after a
full workload.  Exits nonzero on any mismatch; prints the marker line on
success.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.configs import get_config, reduced
from repro.launch.mesh import serve_mesh
from repro.models import serve as SV
from repro.models import transformer as T
from repro.runtime import decode_loop as DL
from repro.runtime.paged import PagedServeEngine


def make_cfg(arch, **over):
    cfg = dataclasses.replace(reduced(get_config(arch)), param_dtype="float32",
                              remat="none")
    return dataclasses.replace(cfg, **over) if over else cfg


def prompts_for(cfg, seed=0):
    """Two shared-prefix + one distinct prompt, all short (CPU GSPMD)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(2, cfg.vocab_size - 1, 12).tolist()
    a = shared + rng.integers(2, cfg.vocab_size - 1, 4).tolist()
    b = shared + rng.integers(2, cfg.vocab_size - 1, 4).tolist()
    c = rng.integers(2, cfg.vocab_size - 1, 9).tolist()
    return [a, b, c]


def step_parity(cfg, params, par):
    """Direct sharded-vs-oracle logits parity for the paged step programs
    (tighter than token parity: 1e-5 on raw logits)."""
    ps, n_pages, slots = 8, 8, 2
    cache0 = SV.init_paged_cache(cfg, slots, n_pages, ps)
    table = jnp.array([[0, 1, -1], [2, 3, -1]], jnp.int32)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size - 1, (slots, ps)),
                       jnp.int32)
    off = jnp.zeros(slots, jnp.int32)
    live = jnp.full(slots, ps, jnp.int32)

    def run(par_):
        lg, cache = SV.chunk_step(cfg, par_, params, cache0, toks, off, live,
                                  table=table)
        lg2, _ = SV.decode_step(cfg, par_, params, cache,
                                {"tokens": jnp.argmax(lg, -1, keepdims=True)},
                                jnp.full(slots, ps, jnp.int32), table=table)
        return jax.device_get(lg), jax.device_get(lg2)

    lg0, lg20 = run(None)
    with par.mesh:
        lg1, lg21 = run(par)
    np.testing.assert_allclose(lg1, lg0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lg21, lg20, rtol=1e-5, atol=1e-5)


def engine_parity(arch, name, *, paged=True, n_host_chunks=0, page_size=8,
                  n_pages=24, spill_pages=0, **over):
    cfg = make_cfg(arch, **over)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    par = serve_mesh(2, 4)
    kw = dict(slots=2, bucket=16, max_new_tokens=4, prefill_chunk=8,
              segment=2, n_host_chunks=n_host_chunks)
    pkw = (dict(kw, page_size=page_size, n_pages=n_pages,
                spill_pages=spill_pages) if paged else kw)
    Eng = PagedServeEngine if paged else DL.ServeEngine
    prompts = prompts_for(cfg)

    e0 = Eng(cfg, params, **pkw)  # single-device oracle
    want = e0.generate(prompts)

    with par.mesh:
        e1 = Eng(cfg, params, par=par, **pkw)
        got = e1.generate(prompts)
        assert got == want, f"{name}: sharded tokens diverge\n{got}\n{want}"
        if paged and e1.radix_enabled:
            got2 = e1.generate(prompts)
            hit = e1.last_stats["prefix_hit_tokens"]
            assert hit > 0, f"{name}: second run should radix-hit"
            want2 = e0.generate(prompts)
            assert got2 == want2, f"{name}: post-radix-hit tokens diverge"
        if n_host_chunks:
            kinds = {leaf.sharding.memory_kind for path, leaf
                     in jax.tree_util.tree_flatten_with_path(e1._pool_cache)[0]
                     if str(path[-1].key) in ("pk", "pv")}
            assert kinds == {"pinned_host"}, f"{name}: pool K/V in {kinds}"
        progs = e1.compiled_programs()
        expect = ({"segment", "reset", "copy", "promote"} if paged
                  else {"segment", "reset"})
        # bounded set: each program compiled AT MOST once (copy/promote
        # stay 0 when no COW / spill re-admit fired, e.g. radix-disabled
        # recurrent layouts)
        assert set(progs) == expect and all(v <= 1 for v in progs.values()) \
            and progs["segment"] == 1 and progs["reset"] == 1, \
            f"{name}: program set grew: {progs}"

    if paged and arch.startswith("llama"):
        with par.mesh:
            step_parity(cfg, params, par)
    print(f"OK {name}")


def spill_parity():
    """Demote -> promote round-trip on the mesh: a tight pool forces LRU
    radix pages into the spill tier, re-serving the original prompts
    promotes them back through the sharded ``promote_page`` scatter, and
    the whole three-workload sequence must match the single-device oracle
    token for token."""
    cfg = make_cfg("llama3.2-1b", num_heads=4, num_kv_heads=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    par = serve_mesh(2, 4)
    pkw = dict(slots=2, bucket=16, max_new_tokens=4, prefill_chunk=8,
               segment=2, page_size=8, n_pages=8, spill_pages=16)
    prompts = prompts_for(cfg)
    rng = np.random.default_rng(7)
    evictors = [rng.integers(2, cfg.vocab_size - 1, 16).tolist()
                for _ in range(3)]

    def run(eng):
        return (eng.generate(prompts), eng.generate(evictors),
                eng.generate(prompts))

    want = run(PagedServeEngine(cfg, params, **pkw))
    with par.mesh:
        e1 = PagedServeEngine(cfg, params, par=par, **pkw)
        got = run(e1)
        assert got == want, f"llama-spill: tokens diverge\n{got}\n{want}"
        st = e1.last_stats
        assert st["spill_promotes"] > 0, \
            f"llama-spill: expected promote-from-spill re-admissions: {st}"
        progs = e1.compiled_programs()
        assert progs["promote"] == 1 and all(v <= 1 for v in progs.values()), \
            f"llama-spill: program set grew: {progs}"
    print("OK llama-spill")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.device_count()
    # hkv=4 divides sp=4 -> pool kv-heads shard over the model axis
    engine_parity("llama3.2-1b", "llama-headshard", num_heads=4,
                  num_kv_heads=4)
    # hkv=2 does NOT divide sp=4 -> in-page sequence fallback (ps=8 % 4 == 0)
    engine_parity("llama3.2-1b", "llama-psfallback", num_heads=4,
                  num_kv_heads=2)
    # recurrent layout on the mesh (radix disabled by design there)
    engine_parity("falcon-mamba-7b", "ssm-paged")
    # dense engine path (no pool) also carries mesh shardings
    engine_parity("llama3.2-1b", "llama-dense", paged=False, num_heads=4,
                  num_kv_heads=4)
    # host streaming on the mesh: ps=4 does NOT divide the 8 devices, the
    # page rows (hkv * dh) do, and the pool must stay host-side
    engine_parity("llama3.2-1b", "llama-psindiv-stream", num_heads=8,
                  num_kv_heads=8, page_size=4, n_pages=48, n_host_chunks=2)
    # demote/promote round-trip + persistence program bound on the mesh
    spill_parity()
    print("ALL SERVE MESH CHECKS PASSED")
