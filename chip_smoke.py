#!/usr/bin/env python3
"""Bring-up smoke of the FPDT training path on a TPU.

  python3 chip_smoke.py               # one chip
  python3 chip_smoke.py --four-chips  # a four-chip host: the mesh phase only

One chip, in order:
  (a) refuses to run unless JAX's first device is a TPU;
  (b) points the persistent compile cache (``JAX_COMPILATION_CACHE_DIR`` if
      set, else ``<checkout>/.jax_cache``) before anything compiles;
  (c) asserts that the placement policy can offload to pinned host memory;
  (d) parity: one FPDT attention layer at llama3.2-1b widths, fwd+bwd, at
      u=4 with offload and the Pallas kernels against the same layer at
      u=1 with the XLA blockwise kernels;
  (e) trains llama3.2-1b at its published widths through
      ``repro.launch.train.run`` (the body of ``python -m
      repro.launch.train``): seq 32768, batch 1, u=8, offload, full remat,
      Pallas attention; the compiled step must hold Mosaic kernels
      (``tpu_custom_call``);
  (f) prints compile time, step times, peak HBM, losses (finite; the
      parameters change every step), the device kind and the depth;
  (g) serves llama3.2-1b widths (depth cut) through the paged engine with
      its KV pool parked in pinned host memory (pages streamed into the
      program, written back whole) and checks the generated tokens against
      the same engine with the pool in device memory, over two workloads
      (the second reuses radix-cached prefix pages).

``--four-chips`` runs only the mesh phase: a (1 data, 4 model) mesh, the
FPDT step (u=8, offload) against the u=1 Ulysses baseline at one sequence
(loss and grad-norm within the stated tolerance), then a few steps at a
4x longer sequence.  It checks what the mesh splits 4 ways (the embedding
and its Adam moments, the batch's sequence dim, the FPDT q/K/V stores in
the compiled step), names what stays replicated, and prints the
per-device peak HBM.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
phase exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "llama3.2-1b"
SEQ = 32768
CHUNKS = 8
# All 16 layers fit one v5e's 16 GB with bf16 Adam moments (fp32 moments
# need 17.5 GB).
LAYERS = 16
OPT_STATE_DTYPE = "bfloat16"
STEPS = 3
PARITY_SEQ, PARITY_U = 8192, 4
# bf16 parity: max |a - b| over max |b|, per output and gradient
TOL = 2e-2
MESH_LAYERS = 4
MESH_SEQ, MESH_LONG_SEQ = 32768, 131072
# serve phase: f32 weights, so host-streamed and on-device attention (which
# differ only in summation order) pick the same greedy tokens
SERVE_LAYERS, SERVE_SLOTS, SERVE_NEW = 4, 2, 16
SERVE_PROMPTS = (300, 170, 450)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def rel_err(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))), float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def parity_phase():
    """(d) FPDT u=4 (offload, Pallas) vs u=1 (XLA blockwise), fwd+bwd."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import fpdt
    from repro.core.parallel import ParallelContext
    from repro.models import layers as L

    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(0)
    p = L.init_attn(cfg, key, jnp.bfloat16)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, PARITY_SEQ, cfg.d_model), jnp.bfloat16)
    do = jax.random.normal(jax.random.fold_in(key, 2), (1, PARITY_SEQ, cfg.q_dim), jnp.bfloat16)

    def compiled(u, impl):
        c = dataclasses.replace(cfg, fpdt_chunks=u, fpdt_offload=u > 1)
        par = ParallelContext(mesh=None, attn_impl=impl)

        def f(x, p):
            o = fpdt.fpdt_attention(c, par, p, x, kind="local")
            return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True)).lower(x, p).compile()

    fpdt_step = compiled(PARITY_U, "pallas")
    check("tpu_custom_call" in fpdt_step.as_text(),
          "the FPDT parity step holds no Mosaic kernel (tpu_custom_call)")
    (_, o), g = fpdt_step(x, p)
    (_, o_ref), g_ref = compiled(1, "xla_flash")(x, p)
    names = ["out", "dx"] + [f"d{k}" for k in sorted(g_ref[1])]
    got = [o, g[0]] + [g[1][k] for k in sorted(g_ref[1])]
    want = [o_ref, g_ref[0]] + [g_ref[1][k] for k in sorted(g_ref[1])]
    worst = 0.0
    for n, a, b in zip(names, got, want):
        abs_err, rel = rel_err(a, b)
        worst = max(worst, rel)
        log(f"parity {n}: max abs err {abs_err:.3e}, relative to max |ref| {rel:.3e}")
    check(worst <= TOL, f"parity error {worst:.3e} exceeds tolerance {TOL}")
    log(f"parity ok: seq {PARITY_SEQ}, u={PARITY_U} offload+pallas vs u=1 xla_flash, "
        f"worst relative error {worst:.3e} <= {TOL}")


def train_args(*extra):
    from repro.launch.train import build_parser

    return build_parser().parse_args([
        "--arch", ARCH, "--batch", "1", "--remat", "full", "--log-every", "1",
        *extra])


def train_phase(dev):
    """(e)+(f) full-width FPDT training through the train CLI's body."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.train import run

    samples = []

    def on_step(step, params, metrics):
        leaves = jax.tree.leaves(params)
        samples.append([np.asarray(l.reshape(-1)[:4096].astype(np.float32)) for l in leaves])

    full = get_config(ARCH).num_layers
    log(f"depth: {LAYERS} of {full} layers" + ("" if LAYERS == full else f" (cut by {full - LAYERS})")
        + f", opt state {OPT_STATE_DTYPE}")
    tr = run(train_args("--seq", str(SEQ), "--chunks", str(CHUNKS), "--offload",
                        "--steps", str(STEPS), "--num-layers", str(LAYERS),
                        "--opt-state-dtype", OPT_STATE_DTYPE), on_step=on_step)
    hist = tr.loop.history
    check(len(hist) == STEPS, f"ran {len(hist)} of {STEPS} steps")
    check("tpu_custom_call" in tr.compiled.as_text(),
          "the train step holds no Mosaic kernel (tpu_custom_call)")
    losses = [h["loss"] for h in hist]
    check(all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    tr.params = tr.opt_state = None  # free HBM before the seed re-init
    prev = _init_sample(tr)
    for i, cur in enumerate(samples):
        changed = sum(not np.array_equal(a, b) for a, b in zip(cur, prev))
        check(changed > len(cur) // 2,
              f"step {i + 1} changed only {changed} of {len(cur)} parameter leaves")
        prev = cur
    mem = dev.memory_stats()
    times = [h["dt"] for h in hist]
    steady = sum(times[1:]) / len(times[1:])
    log(f"train: {tr.cfg.name} d_model {tr.cfg.d_model}, {tr.cfg.num_heads}/{tr.cfg.num_kv_heads} "
        f"heads, d_ff {tr.cfg.d_ff}, vocab {tr.cfg.vocab_size}, seq {SEQ}, batch 1, u={CHUNKS}, "
        f"offload on, remat full, Pallas attention")
    log(f"compile {tr.compile_s:.1f} s; step wall times (s, to block_until_ready): "
        + ", ".join(f"{t:.3f}" for t in times))
    log(f"mean of steps 2..{STEPS}: {steady:.3f} s = {SEQ / steady:.0f} tokens/s")
    log(f"losses: " + ", ".join(f"{v:.4f}" for v in losses))
    ma = tr.compiled.memory_analysis()
    log(f"peak HBM in use: {mem['peak_bytes_in_use'] / 2**30:.2f} GiB "
        f"of {mem['bytes_limit'] / 2**30:.2f} GiB (step program: arguments "
        f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, temporaries {ma.temp_size_in_bytes / 2**30:.2f} GiB)")
    log("tpu_custom_call present in the compiled step: True")


def _init_sample(tr):
    """Parameter sample at initialisation, rebuilt from the seed."""
    import jax
    import numpy as np

    from repro.models import transformer as T

    params = jax.jit(lambda k: T.init_params(tr.cfg, k))(jax.random.PRNGKey(0))
    out = [np.asarray(l.reshape(-1)[:4096].astype(np.float32)) for l in jax.tree.leaves(params)]
    del params
    return out


def serve_phase():
    """(g) paged serving with the KV pool host-parked vs in device memory."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.parallel import ParallelContext
    from repro.models import transformer as T
    from repro.runtime.paged import PagedServeEngine

    cfg = dataclasses.replace(get_config(ARCH), num_layers=SERVE_LAYERS,
                              param_dtype="float32", remat="none")
    params = jax.jit(lambda k: T.init_params(cfg, k))(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    shared = rng.integers(2, cfg.vocab_size, 128).tolist()
    prompts = [shared + rng.integers(2, cfg.vocab_size, n - 128).tolist()
               for n in SERVE_PROMPTS]
    kw = dict(slots=SERVE_SLOTS, bucket=512, max_new_tokens=SERVE_NEW,
              page_size=16, prefill_chunk=64, segment=8)
    par = ParallelContext(mesh=None)
    engines = {"host pool": PagedServeEngine(cfg, params, n_host_chunks=1, par=par, **kw),
               "device pool": PagedServeEngine(cfg, params, **kw)}
    outs = {}
    for name, eng in engines.items():
        outs[name] = []
        for run_i in range(2):
            t0 = time.perf_counter()
            outs[name].append(eng.generate(prompts))
            st = eng.last_stats
            log(f"serve {name}, workload {run_i + 1}: {time.perf_counter() - t0:.2f} s "
                f"(compile included in workload 1), prefix-hit tokens {st['prefix_hit_tokens']}, "
                f"COW copies {st['cow_copies']}, programs {eng.compiled_programs()}")
    kinds = {str(path[-1].key): leaf.sharding.memory_kind for path, leaf
             in jax.tree_util.tree_flatten_with_path(engines["host pool"]._pool_cache)[0]}
    check(kinds["pk"] == kinds["pv"] == "pinned_host",
          f"the host-streamed pool's K/V are in {kinds['pk']}/{kinds['pv']}, not pinned_host")
    check(engines["host pool"].last_stats["prefix_hit_tokens"] > 0,
          "the second workload reused no cached prefix page")
    progs = engines["host pool"].compiled_programs()
    check(progs["segment"] == progs["reset"] == 1, f"the serve programs recompiled: {progs}")
    got, want = outs["host pool"], outs["device pool"]
    same = sum(a == b for w in range(2) for ga, gb in zip(got[w], want[w]) for a, b in zip(ga, gb))
    total = sum(len(g) for w in want for g in w)
    log(f"serve: {cfg.name} widths, {SERVE_LAYERS} layers, f32, prompts {list(SERVE_PROMPTS)}, "
        f"{SERVE_NEW} new tokens each; host-pool tokens equal device-pool tokens: {same}/{total}")
    check(got == want, "the host-streamed pool generated different tokens")


def four_chip_phase():
    """FPDT (u=8, offload) vs Ulysses (u=1) on a (1, 4) mesh, then a longer run."""
    import jax

    from repro.launch.train import run

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, {len(devs)} visible")

    def one(seq, chunks, steps, offload):
        rec = []
        extra = ["--offload"] if offload else []
        tr = run(train_args("--mesh", "1x4", "--seq", str(seq), "--chunks", str(chunks),
                            "--steps", str(steps), "--num-layers", str(MESH_LAYERS), *extra),
                 on_step=lambda s, p, m: rec.append((float(m["loss"]), float(m["grad_norm"]))))
        check("tpu_custom_call" in tr.compiled.as_text(),
              "the mesh step holds no Mosaic kernel (tpu_custom_call)")
        placement_report(tr, seq, chunks)
        check(all(math.isfinite(v) for r in rec for v in r), f"non-finite loss/grad norm: {rec}")
        times = [h["dt"] for h in tr.loop.history]
        log(f"mesh 1x4 seq {seq} u={chunks} offload={offload}: compile {tr.compile_s:.1f} s, "
            f"step times " + ", ".join(f"{t:.3f}" for t in times) + " s, (loss, grad norm) "
            + ", ".join(f"({a:.5f}, {b:.5f})" for a, b in rec))
        return rec

    log(f"mesh phase: {ARCH} full widths, {MESH_LAYERS} layers, (1 data, 4 model) mesh")
    base = one(MESH_SEQ, 1, 1, False)[0]
    got = one(MESH_SEQ, CHUNKS, 1, True)[0]
    dl = abs(got[0] - base[0]) / abs(base[0])
    dg = abs(got[1] - base[1]) / abs(base[1])
    log(f"FPDT vs Ulysses at seq {MESH_SEQ}: loss rel diff {dl:.3e}, grad-norm rel diff {dg:.3e} "
        f"(tolerance {TOL})")
    check(dl <= TOL and dg <= TOL, "FPDT and the Ulysses baseline disagree")
    one(MESH_LONG_SEQ, MESH_LONG_SEQ * CHUNKS // MESH_SEQ, 2, True)  # same chunk length
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs[:4]]
    log("per-device peak HBM in use (GiB): " + ", ".join(f"{d.id}: {b / 2**30:.2f}" for d, b in zip(devs, peaks)))
    check(min(peaks) > 0.5 * max(peaks), "device memory is not spread over the 4 chips")


def placement_report(tr, seq, chunks):
    """What the (1, 4) mesh splits 4 ways, and what it replicates.

    Weights and their Adam moments shard over the data axis, which has one
    device on this mesh, so they stay replicated on purpose; the embedding
    splits d over the model axis.  The batch and the FPDT stores split the
    sequence / heads over the model axis (Ulysses)."""
    import jax

    def split4(leaf):
        return leaf.addressable_shards[0].data.size * 4 == leaf.size

    def leaves(tree):
        return [(jax.tree_util.keystr(p), l) for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]]

    for what, tree in (("parameter", tr.params), ("Adam moment", tr.opt_state.m)):
        emb = [l for k, l in leaves(tree) if "embed" in k]
        check(emb and all(split4(l) for l in emb), f"the {what} embedding is not split 4 ways")
        rest = [k for k, l in leaves(tree) if not split4(l)]
        log(f"{what}s: embedding split 4 ways; replicated on purpose (data axis of 1): "
            f"{len(rest)} of {len(leaves(tree))} leaves")
    tokens = tr.compiled.input_shardings[0][2]["tokens"]
    check(tokens.shard_shape((1, seq)) == (1, seq // 4),
          f"the batch is not sequence-split: {tokens.spec}")
    log(f"batch tokens per device {tokens.shard_shape((1, seq))}")
    if chunks == 1:
        return
    cfg, hlo = tr.cfg, tr.compiled.as_text()
    cq, hq, hkv = seq // chunks, cfg.num_heads, cfg.num_kv_heads

    def store(heads, length):
        return f"[{chunks},1,{heads},{length},{cfg.head_dim}]"

    # heads over the model axis where they divide it, else the chunk's sequence
    kv = store(hkv // 4, cq) if hkv % 4 == 0 else store(hkv, cq // 4)
    per_dev = [store(hq // 4, cq), kv]
    check(all(s in hlo for s in per_dev) and store(hq, cq) not in hlo,
          f"the compiled step holds no 4-way split q/K/V stores {per_dev}")
    log("FPDT q/K/V stores per device " + ", ".join(per_dev)
        + f" (the whole q store {store(hq, cq)} is absent)")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    args = ap.parse_args()
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[smoke] FAIL: no repro package under {src}; run from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: JAX's first device is {dev.platform}, not a TPU", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache
    from repro.runtime.placement import default_policy

    log(f"device: {dev.device_kind} x{len(jax.devices())}; jax {jax.__version__}; "
        f"compile cache {setup_compile_cache()}")
    try:
        pol = default_policy()
        check(pol.can_offload, f"no offload pool on this backend: {pol}")
        log(f"offload: can_offload=True ({pol.device_kind} -> {pol.host_kind})")
        t0 = time.perf_counter()
        if args.four_chips:
            four_chip_phase()
        else:
            parity_phase()
            train_phase(dev)
            serve_phase()
        log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
