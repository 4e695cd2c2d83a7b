"""The main-path kernels compile for a TPU v5e, without the chip.

Each case compiles with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology at real widths, so a block shape, layout or VMEM use
that Mosaic refuses fails here, not on the chip.  The topology is described
inside a fixture (skipping where it cannot be), never at import.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import kernel as FK
from repro.kernels.linear_scan import kernel as LK

# llama3.2-1b attention at an FPDT chunk of 4096 tokens, 512-token blocks
B, HQ, HKV, S, D, BLOCK = 1, 32, 8, 4096, 64, 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(dev, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(dev))


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_fwd_compiles(one_chip):
    q = _sds(one_chip, (B, HQ, S, D))
    k = _sds(one_chip, (B, HKV, S, D))
    acc = _sds(one_chip, (B, HQ, S, D), jnp.float32)
    row = _sds(one_chip, (B, HQ, S), jnp.float32)
    off = _sds(one_chip, (), jnp.int32)

    def f(q, k, v, acc, m, l, off):
        return FK.flash_fwd(q, k, v, (acc, m, l), q_offset=off, k_offset=0,
                            block_q=BLOCK, block_k=BLOCK, interpret=False)

    assert "tpu_custom_call" in _hlo(f, q, k, k, acc, row, row, off)


@pytest.mark.parametrize("kernel", ["flash_bwd_dq", "flash_bwd_dkv"])
def test_flash_bwd_compiles(one_chip, kernel):
    q = _sds(one_chip, (B, HQ, S, D))
    k = _sds(one_chip, (B, HKV, S, D))
    do = _sds(one_chip, (B, HQ, S, D), jnp.float32)
    row = _sds(one_chip, (B, HQ, S), jnp.float32)

    def f(q, k, v, do, L, delta):
        return getattr(FK, kernel)(q, k, v, do, L, delta, window=1024,
                                   block_q=BLOCK, block_k=BLOCK, interpret=False)

    assert "tpu_custom_call" in _hlo(f, q, k, k, do, row, row)


def test_flash_refuses_untiled_block(one_chip):
    q = _sds(one_chip, (B, HQ, 320, D))
    with pytest.raises(ValueError, match="128-lane"):
        jax.jit(lambda q: FK.flash_fwd(q, q, q, block_q=160, block_k=160,
                                       interpret=False)).lower(q)


def test_linear_scan_compiles(one_chip):
    # recurrentgemma-9b RG-LRU width (lru_width == d_model == 4096)
    width = get_config("recurrentgemma-9b").d_model
    a = _sds(one_chip, (1, 4096, width), jnp.float32)
    h0 = _sds(one_chip, (1, width), jnp.float32)
    hlo = _hlo(lambda a, b, h: LK.linear_scan(a, b, h, interpret=False), a, a, h0)
    assert "tpu_custom_call" in hlo


@pytest.fixture(scope="module")
def fpdt_offload_hlo(one_chip):
    """Compiled HLO of one scan-compiled FPDT attention layer, fwd+bwd, u=8,
    offload on."""
    from repro.core import fpdt
    from repro.core.parallel import ParallelContext
    from repro.models import layers as L
    from repro.runtime.placement import PlacementPolicy

    cfg = dataclasses.replace(get_config("llama3.2-1b"), fpdt_chunks=8, fpdt_offload=True)
    par = ParallelContext(mesh=None, attn_impl="pallas",
                          placement=PlacementPolicy.probe(one_chip))
    assert par.offload_active
    seq = 8 * 1024
    p = jax.eval_shape(lambda: L.init_attn(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    p = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), p)
    x = _sds(one_chip, (1, seq, cfg.d_model))

    def loss(x, p):
        return fpdt.fpdt_attention(cfg, par, p, x, kind="local").astype(jnp.float32).sum()

    # the kernels pick interpret mode from the process's (CPU) backend
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(FK, "_default_interpret", lambda: False)
        return _hlo(jax.grad(loss, argnums=(0, 1)), x, p)


def test_fpdt_layer_compiles_with_offload(fpdt_offload_hlo):
    """One scan-compiled FPDT attention layer, fwd+bwd, u=8, offload on."""
    hlo = fpdt_offload_hlo
    assert "tpu_custom_call" in hlo
    assert "pinned_host" in hlo or "S(5)" in hlo  # the KV/q stores live on host


KERNELS = (FK.FWD, FK.DQ, FK.DKV)


def _instructions(hlo):
    """(name, opcode, op_name components, line) of each HLO instruction."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*?[\]\}\)] ([a-z][a-z0-9-]*)\(", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), op.group(1).split("/") if op else [], line))
    return out


def test_fpdt_layer_names_its_kernels_and_offload_ops(fpdt_offload_hlo):
    """Each Mosaic kernel's op_name ends ``<kernel>/pallas_call`` with one of
    the three kernel names and its instruction is named after it; no other
    instruction or scope is named ``flash_attention...``; the host stores and
    fetches sit under ``fpdt.store`` and ``fpdt.fetch``."""
    ins = _instructions(fpdt_offload_hlo)
    kernels = [i for i in ins if 'custom_call_target="tpu_custom_call"' in i[3]]
    assert {i[2][-2] for i in kernels} == set(KERNELS)
    for name, _, comps, _ in kernels:
        assert comps[-1] == "pallas_call"
        assert sum(c in KERNELS for c in comps) == 1
        assert name.split(".")[0] == comps[-2]
    for name, opcode, comps, _ in ins:
        if (name, opcode) in {(k[0], k[1]) for k in kernels}:
            continue
        assert not name.startswith("flash_attention"), name
        named = [c for c in comps if c.startswith("flash_attention")]
        # only the kernels' own tuple outputs share their op_name
        assert not named or (opcode == "get-tuple-element" and comps[-1] == "pallas_call"), name
    host_starts = {i[0] for i in ins if i[1].endswith("-start") and "S(5)" in i[3]}
    stores = [i for i in ins if i[1] in ("dynamic-update-slice-start", "dynamic-update-slice-done")]
    fetches = [i for i in ins if (i[1] == "dynamic-slice-start" and i[0] in host_starts) or (
        i[1] == "dynamic-slice-done" and re.search(r"\(%(\S+)\)", i[3]).group(1) in host_starts)]
    assert stores and fetches
    assert all("fpdt.store" in i[2] for i in stores)
    # the Fig. 7 backward's first K and V fetch (chunk 0, a constant index) is
    # rebuilt by XLA without an op_name; every other fetch is under fpdt.fetch
    assert all("fpdt.fetch" in i[2] for i in fetches if i[2])
    assert len([i for i in fetches if not i[2]]) <= 4


def test_fpdt_train_step_has_no_host_executed_copies(one_chip):
    """A 2-layer layer-scanned dense train step, u=4, offload on, remat full:
    the FPDT stores stay in pinned host memory, and no instruction runs on the
    host.  A zero store hoisted out of the layer loop would be copied to each
    layer by a ``HostExecute`` call; one layer has no loop to hoist out of."""
    from repro.configs import ModelConfig
    from repro.core.parallel import ParallelContext
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.runtime import train_loop
    from repro.runtime.placement import PlacementPolicy

    u, seq = 4, 8192
    cfg = ModelConfig(name="dense-2l", family="dense", num_layers=2, d_model=512,
                      num_heads=4, num_kv_heads=4, head_dim=128, d_ff=2048,
                      vocab_size=1024, fpdt_chunks=u, mlp_chunks=2 * u,
                      fpdt_offload=True, remat="full")
    par = ParallelContext(mesh=None, attn_impl="pallas",
                          placement=PlacementPolicy.probe(one_chip))
    assert par.offload_active
    oc = adamw.OptConfig()
    params = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw.init(oc, p), params)
    params, opt = (jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), t)
                   for t in (params, opt))
    batch = {k: _sds(one_chip, (1, seq), jnp.int32) for k in ("tokens", "labels")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(FK, "_default_interpret", lambda: False)
        hlo = _hlo(train_loop.make_train_step(cfg, par, oc), params, opt, batch)
    assert "HostExecute" not in hlo
    assert 'async_execution_thread="host"' not in hlo
    stores = [i for i in _instructions(hlo) if i[1] == "dynamic-update-slice-start"]
    assert stores and all("S(5)" in i[3] for i in stores)


def _computations(hlo):
    """{computation: [(name, opcode, line)]}, instructions in schedule order."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) .*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = re.match(r"\s*(?:ROOT )?%(\S+) = .*?[\]\}\)] ([a-z][a-z0-9-]*)\(", line)
            if m:
                cur.append((m.group(1), m.group(2), line))
    return comps


def _called(line):
    """Computations a while, call or conditional instruction runs."""
    names = re.findall(r"(?:body|condition|to_apply|true_computation|false_computation)=%([\w.\-]+)",
                       line)
    m = re.search(r"branch_computations=\{([^}]*)\}", line)
    return names + (re.findall(r"%([\w.\-]+)", m.group(1)) if m else [])


def _host_fetches(hlo):
    """(op_name, flash kernels it overlaps) of every host fetch: an async
    dynamic-slice-start that reads pinned host memory (``S(5)``).  It
    overlaps the flash kernels scheduled between it and its done, directly
    or inside a while, call or conditional scheduled there."""
    comps = _computations(hlo)
    memo = {}

    def kernels(name, line):
        if 'custom_call_target="tpu_custom_call"' in line and name.startswith("flash_attention"):
            return {name.split(".")[0]}
        out = set()
        for c in _called(line):
            if c not in memo:
                memo[c] = set()  # cycle guard
                memo[c] = set().union(*(kernels(n, l) for n, _, l in comps.get(c, ())))
            out |= memo[c]
        return out

    fetches = []
    for ins in comps.values():
        for k, (name, opcode, line) in enumerate(ins):
            if opcode != "dynamic-slice-start" or "S(5)" not in line:
                continue
            done = next(j for j, (_, op2, l2) in enumerate(ins)
                        if op2 == "dynamic-slice-done" and f"(%{name})" in l2)
            op = re.search(r'op_name="([^"]*)"', line)
            fetches.append((op.group(1) if op else "",
                            set().union(*(kernels(n, l) for n, _, l in ins[k + 1:done]))))
    return fetches


def test_fpdt_dense_fetches_overlap_flash_kernels(one_chip):
    """A 2-layer dense train step, u=8, offload on, remat full: every host
    fetch a dense schedule issues inside a double-buffered chunk loop (two
    loops deep in the FPDT function) has a flash kernel scheduled between
    its start and its done -- the KV chunk fetch of the forward and of its
    recomputation, the q chunk fetch of the Fig. 7 backward -- and the
    loops' first fetches (prologues) are the only others besides the Fig. 7
    KV prefetch.  The schedule holds sites, not counts: each in-loop site
    runs once per loop trip past the first, which ``overlapped_fetches``
    counts.  (At u=4 the forward has one group with a one-trip KV loop, and
    XLA flattens it: no loop is left to overlap.)"""
    from repro.configs import ModelConfig
    from repro.core import fpdt
    from repro.core.parallel import ParallelContext
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.runtime import train_loop
    from repro.runtime.placement import PlacementPolicy

    u, seq = 8, 8192
    cfg = ModelConfig(name="dense-2l", family="dense", num_layers=2, d_model=512,
                      num_heads=4, num_kv_heads=4, head_dim=128, d_ff=2048,
                      vocab_size=1024, fpdt_chunks=u, mlp_chunks=2 * u,
                      fpdt_offload=True, remat="full")
    par = ParallelContext(mesh=None, attn_impl="pallas",
                          placement=PlacementPolicy.probe(one_chip))
    oc = adamw.OptConfig()
    params = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw.init(oc, p), params)
    params, opt = (jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), t)
                   for t in (params, opt))
    batch = {k: _sds(one_chip, (1, seq), jnp.int32) for k in ("tokens", "labels")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(FK, "_default_interpret", lambda: False)
        fetches = _host_fetches(_hlo(train_loop.make_train_step(cfg, par, oc), params, opt, batch))

    def loops(op_name):  # loops around the fetch inside the FPDT function
        return op_name.partition("jit(f)/")[2].count("while/body")

    def fig7(op_name):  # the backward proper, not the forward it recomputes
        return "transpose" in op_name and "rematted_computation" not in op_name

    in_loop = [(op, ks) for op, ks in fetches if loops(op) >= 2]
    # K and V of the forward and of its recomputation; q of Fig. 7
    assert sorted(sorted(ks) for op, ks in in_loop if not fig7(op)) == [[FK.FWD]] * 4
    assert [ks for op, ks in in_loop if fig7(op)] == [{FK.DQ, FK.DKV}]
    # the rest: K and V prefetched by Fig. 7's outer loop, and the prologues
    # (the forward's K and V in two passes, Fig. 7's first q, K and V)
    assert len(fetches) - len(in_loop) == 2 + 4 + 3
    # the sites the helper counts, from the compiled step's own settings
    assert fpdt.overlapped_fetches(
        u, cq=seq // u, head_groups=fpdt.head_groups(par, "local", cfg.num_kv_heads),
        offload=cfg.fpdt_offload and par.offload_to_host) == (9, 28)


def test_paged_serve_keeps_host_pool_off_device(one_chip):
    """The host-streamed paged serve programs at llama3.2-1b widths: the
    pool's K/V enter and leave in pinned host memory, and no program
    copies the pool into device memory (device temporaries stay well under
    the pool's size)."""
    import functools

    from repro.core.parallel import ParallelContext
    from repro.models import serve as SV
    from repro.models import transformer as T
    from repro.runtime import decode_loop as DL
    from repro.runtime import paged as PG
    from repro.runtime.placement import PlacementPolicy

    cfg = dataclasses.replace(get_config("llama3.2-1b"), remat="none")
    par = ParallelContext(mesh=None, placement=PlacementPolicy.probe(one_chip))
    assert par.offload_active
    slots, n_pages, ps, max_pages, cp = 4, 2048, 16, 64, 64
    host = SingleDeviceSharding(one_chip, memory_kind="pinned_host")

    def place(path, s):
        on_host = str(getattr(path[-1], "key", "")) in ("pk", "pv")
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=(
            host if on_host else SingleDeviceSharding(one_chip)))

    cache = jax.tree_util.tree_map_with_path(place, jax.eval_shape(
        lambda: SV.init_paged_cache(cfg, slots, n_pages, ps)))
    pool = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(cache)
               if l.sharding.memory_kind == "pinned_host")
    params = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    i32 = functools.partial(_sds, one_chip, dtype=jnp.int32)

    def seg(params, cache, mode, tok, pos, key, rem, pfill, pend, plen, table):
        return DL.mixed_segment(cfg, par, params, cache, mode, tok, pos, key, rem,
                                pfill, pend, plen, num_steps=2, prefill_chunk=cp,
                                n_host_chunks=1, table=table)

    seg_args = (params, cache, i32((slots,)), i32((slots, 1)), i32((slots,)),
                _sds(one_chip, (2,), jnp.uint32), i32((slots,)), i32((slots,)),
                i32((slots, 4 * cp)), i32((slots,)), i32((slots, max_pages)))
    rows = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), jax.eval_shape(
        lambda c: SV.page_rows(None, c, 0), cache))
    i = i32(())
    programs = {
        "segment": (seg, seg_args),
        "copy": (functools.partial(PG.copy_page, par=par), (cache, i, i, i)),
        "promote": (functools.partial(PG.promote_page, par=par), (cache, i, rows, i)),
        "page_rows": (functools.partial(SV.page_rows, par), (cache, i)),
    }
    for name, (fn, args) in programs.items():
        c = jax.jit(fn).lower(*args).compile()
        assert c.memory_analysis().temp_size_in_bytes < pool / 4, name
        if name != "page_rows":
            kinds = [s.memory_kind for s in jax.tree.leaves(c.output_shardings)]
            assert kinds.count("pinned_host") == 2, (name, kinds)  # pk, pv
