"""Serving CLI: batched prefill + scan-compiled multi-token decode.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --batch 4 --prompt-len 64 --gen 32 [--host-kv-chunks 8] \
      [--temperature 0.8 --top-k 40]

The whole generation is ONE jitted ``runtime.decode_loop.decode_tokens``
call (a ``lax.scan`` over steps), so there is a single dispatch for the
entire decode and program size is flat in ``--gen`` and
``--host-kv-chunks``.  ``--per-token`` keeps the legacy one-jitted-call-
per-token loop for A/B timing (and is the only mode for the audio-frame
frontend, which feeds embeddings instead of token ids).

``--engine`` switches to the continuous-batching ``ServeEngine`` (the
fused mixed-step scheduler: chunked prefill interleaved with decode, see
``docs/serving.md``): ``--requests`` mixed-length prompts over ``--batch``
slots, ``--prefill-chunk`` tokens streamed into a refilling slot per step
while the others decode.  ``--blocking`` runs the stop-the-world refill
baseline instead for A/B.  ``--paged`` swaps the dense per-slot cache for
the slot-shared paged pool with radix prefix reuse
(``runtime/paged.py``): ``--page-size`` tokens per page, ``--n-pages``
physical pages (0 = dense-equivalent), ``--shared-prefix`` prepends a
common system prompt to every request to exercise the radix hits, and
the run reports prefix-hit and page-occupancy stats.  ``--spill-pages N``
adds the host-resident spill tier (evicted radix pages demote instead of
dropping) and ``--kv-store PATH`` persists the prefix cache across runs:
restored at startup when the file exists, saved after the workload — a
restarted server re-serves a shared system prompt as radix hits.
``--sched slo`` (with ``--paged``) swaps in the SLO-aware scheduler
(``runtime/paged.py::SLOPagedServeEngine``): short prompts become the
priority-0 interactive tier, long ones best-effort batch; low-priority
slots are preempted via the radix/spill publish-release path and
``--prefill-budget N`` caps prefill chunks per burst.  ``--sched fifo``
runs the same engine in arrival-order mode for A/B.

``--mesh AxB`` shards each engine over an (A data, B model) device mesh
(paged pool kv-heads over ``model`` per ``models/serve.py``), ``--replicas
N`` runs N such engines on disjoint device slices behind the
session-affine router (``launch/router.py``; ``--router rr`` is the
locality-shredding baseline), with per-replica request/prefix-hit stats.
On CPU the device count is forced automatically (as train.py's
``--mesh`` does).

The router survives replica failure by default: ``--fault-plan
"1:raise@2"`` injects a deterministic crash (``launch/faults.py``) to
watch it happen, ``--retry``/``--dispatch-timeout`` tune the
suspect-state retry budget and the stall deadline, and
``--shared-kv-store DIR`` gives replicas a shared prefix-cache
directory so a dead replica's published pages restore into survivors
and its re-homed sessions resume warm (``prefix_hit_tokens > 0``
instead of a cold prefill).  Failover stats (deaths, retries, re-homed
sessions, recovered prefix tokens) print alongside the per-replica
ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import time


class _MeshReplica:
    """One sharded engine + its mesh, entered around every dispatch — the
    router stays framework-free and replicas stay self-contained."""

    def __init__(self, engine, par):
        self.engine, self.par = engine, par

    def generate(self, prompts):
        with self.par.mesh:
            return self.engine.generate(prompts)

    # prefix-cache persistence proxies: the shared KV store publishes /
    # restores through the replica, and page reads touch mesh-sharded
    # arrays, so they run under the replica's mesh like generate()
    def save_kv_store(self, path):
        with self.par.mesh:
            return self.engine.save_kv_store(path)

    def restore_kv_store(self, path):
        with self.par.mesh:
            return self.engine.restore_kv_store(path)

    @property
    def last_stats(self):
        return self.engine.last_stats


def _export_telemetry(args, telemetries):
    """--trace-out / --metrics-out: dump the run's telemetry to disk."""
    from repro.runtime import telemetry as TM

    if args.trace_out:
        doc = TM.write_chrome_trace(args.trace_out, telemetries)
        print(f"[telemetry] wrote {len(doc['traceEvents'])} trace events "
              f"to {args.trace_out} (open in Perfetto / chrome://tracing)")
    if args.metrics_out:
        TM.write_prometheus(args.metrics_out, telemetries)
        print(f"[telemetry] wrote metrics registry to {args.metrics_out}")


def _engine_main(args):
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.core.parallel import ParallelContext
    from repro.models import transformer as T
    from repro.runtime import decode_loop as DL

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(cfg, remat="none")
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size, size=args.shared_prefix).tolist()
    lens = rng.integers(max(1, args.prompt_len // 4), args.prompt_len + 1,
                        size=args.requests)
    prompts = [shared + rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in lens]
    if args.mesh:
        return _mesh_engine_main(args, cfg, params, prompts)
    par = ParallelContext(mesh=None) if args.host_kv_chunks else None
    bucket = args.prompt_len + args.shared_prefix
    kw = dict(slots=args.batch, bucket=bucket, max_new_tokens=args.gen,
              segment=args.segment, n_host_chunks=args.host_kv_chunks,
              sampling=DL.SamplingConfig(temperature=args.temperature,
                                         top_k=args.top_k), par=par)
    if args.paged:
        from repro.runtime.paged import PagedServeEngine, SLOPagedServeEngine

        spill = args.spill_pages
        if args.kv_store and not spill:
            spill = 4 * args.n_pages if args.n_pages else 64  # restore target
        pkw = dict(prefill_chunk=args.prefill_chunk, page_size=args.page_size,
                   n_pages=args.n_pages, spill_pages=spill, **kw)
        if args.sched:
            engine = SLOPagedServeEngine(cfg, params, policy=args.sched,
                                         prefill_budget=args.prefill_budget,
                                         **pkw)
            name = (f"SLO scheduler (policy={args.sched}, page_size="
                    f"{engine.page_size}, prefill_budget="
                    f"{args.prefill_budget})")
            # QoS assignment: short prompts are the latency-sensitive tier
            # (priority 0, staggered arrivals); long ones ride best-effort
            med = int(np.median(lens))
            prompts = [DL.Request(
                tokens=tuple(p), arrival=i,
                priority=0 if lens[i] <= med else 1,
                itl_slo=8.0 if lens[i] <= med else float("inf"),
                tier="interactive" if lens[i] <= med else "batch")
                for i, p in enumerate(prompts)]
        else:
            engine = PagedServeEngine(cfg, params, **pkw)
            name = (f"paged pool (page_size={engine.page_size}, "
                    f"n_pages={engine.n_pages}, prefill_chunk={engine.cp}"
                    + (f", spill_pages={spill}" if spill else "") + ")")
        if args.kv_store:
            import os

            if os.path.exists(args.kv_store):
                n = engine.restore_kv_store(args.kv_store)
                print(f"[kv-store] restored {n} prefix pages from "
                      f"{args.kv_store}")
    elif args.blocking:
        engine = DL.BlockingServeEngine(cfg, params, **kw)
        name = "blocking baseline"
    else:
        engine = DL.ServeEngine(cfg, params, prefill_chunk=args.prefill_chunk,
                                **kw)
        name = f"fused scheduler (prefill_chunk={engine.cp})"
    t0 = time.perf_counter()
    outs = engine.generate(prompts, key=jax.random.PRNGKey(args.seed))
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    print(f"[{name}] {args.requests} requests (prompt {lens.min()}-"
          f"{lens.max()}{f' +{args.shared_prefix} shared' if args.shared_prefix else ''}) "
          f"over {args.batch} slots: {total} tokens in "
          f"{dt*1e3:.0f} ms ({total/dt:.1f} tok/s incl. compile)")
    steps = engine.last_stats["steps"][1:]  # drop the compile-bearing first
    refill = [s["ms"] for s in steps if s["prefilling"]]
    steady = [s["ms"] for s in steps if not s["prefilling"]]
    if refill and steady:
        print(f"  dispatch wall-clock: steady p50 {np.percentile(steady, 50):.2f} ms, "
              f"refill-active p95 {np.percentile(refill, 95):.2f} ms "
              f"({len(refill)}/{len(steps)} dispatches overlapped a refill)")
    if args.paged:
        st = engine.last_stats
        hit = st["prefix_hit_tokens"] / max(st["prompt_tokens"], 1)
        print(f"  paged pool: prefix hits {st['prefix_hit_tokens']}/"
              f"{st['prompt_tokens']} prompt tokens ({hit:.0%}), "
              f"{st['prefilled_tokens']} prefilled, "
              f"{st['cow_copies']} COW copies, peak occupancy "
              f"{st['pages_peak']}/{engine.n_pages} pages "
              f"({st['radix_pages']} retained in the radix tree)")
        if st.get("spill_pages"):
            print(f"  spill tier: {st['spilled_pages']}/{st['spill_pages']} "
                  f"host pages held, {st['spill_promotes']} promoted back "
                  f"on-device this run")
        if args.sched:
            pre = [r for r in st["requests"] if r["preemptions"]]
            print(f"  scheduler [{st['policy']}]: {st['preemptions']} "
                  f"preemptions ({len(pre)} requests), "
                  f"{st['prefill_pauses']} prefill pauses, "
                  f"{st['deferrals']} deferrals")
        if args.kv_store:
            n = engine.save_kv_store(args.kv_store)
            print(f"[kv-store] saved {n} prefix pages to {args.kv_store}")
    if args.trace_out and args.sched:
        # one-line per-request digest reconstructed from the trace alone
        summ = engine.telemetry.request_summaries()
        for r in sorted(summ):
            s = summ[r]
            print(f"  req {r}: ttft {s['ttft']} steps, itl p50/p95 "
                  f"{s['itl_p50']}/{s['itl_p95']}, queue wait "
                  f"{s['queue_wait']}, {s['n_emitted']} tokens, "
                  f"{s['preemptions']} preemptions, "
                  f"{s['prefix_hit_tokens']} prefix-hit")
    _export_telemetry(args, engine.telemetry)


def _mesh_engine_main(args, cfg, params, prompts):
    """--mesh/--replicas: sharded engine replicas behind the router."""
    import jax

    from repro.launch.mesh import serve_mesh
    from repro.launch.router import ReplicaRouter
    from repro.runtime import decode_loop as DL
    from repro.runtime.paged import PagedServeEngine

    data, model = (int(x) for x in args.mesh.split("x"))
    per, n = data * model, args.replicas
    devs = jax.devices()
    if len(devs) < per * n:
        raise SystemExit(f"--mesh {args.mesh} --replicas {n} needs "
                         f"{per * n} devices, have {len(devs)}")
    fault_plan = {}
    if args.fault_plan:
        from repro.launch.faults import parse_fault_plan
        fault_plan = parse_fault_plan(args.fault_plan)
        bad = [r for r in fault_plan if r >= n]
        if bad:
            raise SystemExit(f"--fault-plan names replicas {bad} but only "
                             f"{n} exist")
    kv_store = None
    if args.shared_kv_store:
        if not args.paged:
            raise SystemExit("--shared-kv-store needs --paged (the prefix "
                             "cache lives in the radix tree)")
        from repro.launch.kvstore import SharedKVStore
        kv_store = SharedKVStore(args.shared_kv_store)
    bucket = args.prompt_len + args.shared_prefix
    kw = dict(slots=args.batch, bucket=bucket, max_new_tokens=args.gen,
              segment=args.segment, n_host_chunks=args.host_kv_chunks,
              prefill_chunk=args.prefill_chunk,
              sampling=DL.SamplingConfig(temperature=args.temperature,
                                         top_k=args.top_k))
    if args.paged:
        spill = args.spill_pages
        if kv_store is not None and not spill:
            # restore lands in the spill tier; give it somewhere to land
            spill = 4 * args.n_pages if args.n_pages else 64
        kw.update(page_size=args.page_size, n_pages=args.n_pages,
                  spill_pages=spill)
    replicas = []
    for r in range(n):
        par = serve_mesh(data, model, devices=devs[r * per:(r + 1) * per])
        with par.mesh:
            eng = (PagedServeEngine if args.paged else DL.ServeEngine)(
                cfg, params, par=par, **kw)
        rep = _MeshReplica(eng, par)
        if r in fault_plan:
            from repro.launch.faults import FaultyReplica
            rep = FaultyReplica(rep, fault_plan[r], name=f"replica{r}")
        replicas.append(rep)
    router = ReplicaRouter(replicas, policy=args.router,
                           max_retries=args.retry,
                           dispatch_timeout=args.dispatch_timeout or None,
                           kv_store=kv_store)
    t0 = time.perf_counter()
    outs = router.generate(prompts)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    st = router.last_stats
    print(f"[{n} x ({data} data x {model} model) mesh replicas, "
          f"router={args.router}] {len(prompts)} requests: {total} tokens "
          f"in {dt*1e3:.0f} ms ({total/dt:.1f} tok/s incl. compile)")
    for rs in st["per_replica"]:
        line = (f"  replica {rs['replica']}: {rs['requests']} requests")
        if "prompt_tokens" in rs:
            hit = rs.get("prefix_hit_tokens", 0)
            line += (f", {rs['prompt_tokens']} prompt tokens"
                     + (f", {hit} prefix-hit" if args.paged else ""))
        print(line)
    if args.router == "affine" and st["spilled"]:
        print(f"  {st['spilled']} requests spilled off their home replica")
    fo = st.get("failover")
    if fo and (fo["deaths"] or fo["retries"] or fo["timeouts"]):
        print(f"  failover: {fo['deaths']} deaths (dead={fo['dead']}), "
              f"{fo['retries']} retries, {fo['timeouts']} timeouts, "
              f"{fo['rehomed_requests']} requests re-homed "
              f"({fo['rehomed_sessions']} sessions), "
              f"{fo['recovered_prefix_tokens']} prefix tokens recovered "
              f"via the shared store ({fo['recovered_pages']} pages "
              f"restored), {fo['live']}/{n} replicas live")
    elif fo:
        print(f"  failover: clean run, {fo['live']}/{n} replicas live")
    if args.trace_out or args.metrics_out:
        tels = [router.telemetry]
        for r, rep in enumerate(replicas):
            # FaultyReplica.__getattr__ forwards to the wrapped replica
            tel = rep.engine.telemetry
            tel.replica = r  # label the replica's track group in the trace
            tels.append(tel)
        if kv_store is not None:
            tels.append(kv_store.telemetry)
        _export_telemetry(args, tels)


def main():
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--host-kv-chunks", type=int, default=0,
                    help="FPDT-for-inference: stream KV from host in N chunks")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples at this temperature")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k best tokens (0 = all)")
    ap.add_argument("--per-token", action="store_true",
                    help="legacy per-token dispatch loop instead of lax.scan")
    ap.add_argument("--engine", action="store_true",
                    help="continuous batching via the fused mixed-step "
                         "scheduler (ServeEngine) instead of one batch")
    ap.add_argument("--blocking", action="store_true",
                    help="with --engine: the stop-the-world refill baseline")
    ap.add_argument("--requests", type=int, default=8,
                    help="with --engine: queued prompts")
    ap.add_argument("--segment", type=int, default=8,
                    help="with --engine: mixed steps per dispatch")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="with --engine: prompt tokens streamed into a "
                         "refilling slot per mixed step (0 = auto)")
    ap.add_argument("--paged", action="store_true",
                    help="with --engine: slot-shared paged KV pool with "
                         "radix-tree prefix reuse (runtime/paged.py)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="with --paged: tokens per pool page")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="with --paged: physical pages in the pool "
                         "(0 = dense-equivalent capacity)")
    ap.add_argument("--spill-pages", type=int, default=0,
                    help="with --paged: host-resident spill tier capacity "
                         "(evicted radix pages demote there instead of "
                         "dropping; 0 = no tier)")
    ap.add_argument("--kv-store", default="",
                    help="with --paged: persist the prefix cache at this "
                         "path — restored at startup when the file exists, "
                         "saved after the run (implies a spill tier)")
    ap.add_argument("--sched", default="", choices=["", "fifo", "slo"],
                    help="with --paged: SLO-aware admission "
                         "(SLOPagedServeEngine) — 'slo' preempts "
                         "lower-priority slots via page spill/publish, "
                         "'fifo' is the arrival-order baseline")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="with --sched slo: prefill chunks a request may "
                         "burn before pausing while co-resident slots "
                         "decode (0 = unbounded)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="with --engine: prepend a common system prompt of "
                         "this many tokens to every request (radix hits)")
    ap.add_argument("--mesh", default="",
                    help="with --engine: shard each engine over an AxB "
                         "(data x model) device mesh, e.g. 1x4 or 2x4")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --mesh: engine replicas on disjoint device "
                         "slices behind the router")
    ap.add_argument("--router", default="affine", choices=["affine", "rr"],
                    help="with --replicas: session-affine dispatch (radix "
                         "locality survives routing) or round-robin")
    ap.add_argument("--fault-plan", default="",
                    help="with --replicas: deterministic fault injection, "
                         "';'-separated R:KIND@N[xC][~S] items (KIND in "
                         "raise/transient/hang), e.g. '1:raise@2' kills "
                         "replica 1 on its 3rd dispatch — the router "
                         "re-homes its work onto survivors")
    ap.add_argument("--retry", type=int, default=1,
                    help="with --replicas: dispatch retries before a "
                         "faulting replica is declared dead")
    ap.add_argument("--dispatch-timeout", type=float, default=0.0,
                    help="with --replicas: wall-clock seconds after which "
                         "a dispatch counts as a fault and its late "
                         "result is discarded (0 = no timeout)")
    ap.add_argument("--shared-kv-store", default="",
                    help="with --replicas + --paged: shared prefix-cache "
                         "directory (one npz per replica); on replica "
                         "death the dead replica's published cache "
                         "restores into survivors so re-homed requests "
                         "resume warm")
    ap.add_argument("--trace-out", default="",
                    help="with --engine: write the run's lifecycle spans "
                         "as Chrome trace-event JSON (open in Perfetto or "
                         "chrome://tracing; one process per engine/router, "
                         "one track per slot)")
    ap.add_argument("--metrics-out", default="",
                    help="with --engine: write the metrics registry as "
                         "Prometheus text exposition after the run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.mesh and not args.engine:
        ap.error("--mesh requires --engine")
    if (args.trace_out or args.metrics_out) and not args.engine:
        ap.error("--trace-out/--metrics-out export engine telemetry; they "
                 "require --engine")
    if (args.fault_plan or args.shared_kv_store) and not args.mesh:
        ap.error("--fault-plan/--shared-kv-store act on the replica "
                 "router; they require --mesh (and --replicas > 1 to "
                 "have anywhere to fail over to)")
    if args.sched and not args.paged:
        ap.error("--sched requires --paged (preemption spills KV pages)")
    if args.sched and args.mesh:
        ap.error("--sched is single-engine for now; route QoS requests to "
                 "sharded replicas via launch/router.py instead")
    if args.mesh:
        try:
            data, model = (int(x) for x in args.mesh.split("x"))
        except ValueError:
            ap.error(f"--mesh must look like AxB, got {args.mesh!r}")
        import os

        # force enough fake CPU devices BEFORE jax import (as train.py's
        # --mesh does); the flag only applies to the CPU backend
        if "--xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{data * model * args.replicas}").strip()
    if args.engine:
        return _engine_main(args)
    if args.host_kv_chunks and (args.prompt_len + args.gen) % args.host_kv_chunks:
        # models/serve.py would silently fall back to on-device attention
        ap.error(f"--host-kv-chunks {args.host_kv_chunks} must divide the "
                 f"cache length prompt-len+gen={args.prompt_len + args.gen}")

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.core.parallel import ParallelContext
    from repro.models import serve as SV
    from repro.models import transformer as T
    from repro.runtime import decode_loop as DL

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(cfg, remat="none")
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    max_len = args.prompt_len + args.gen
    b = args.batch

    if cfg.frontend == "audio_frames":
        batch = {"frame_embeds": jax.random.normal(key, (b, args.prompt_len, cfg.d_model),
                                                   jnp.dtype(cfg.param_dtype))}
    elif cfg.frontend == "vision_patches":
        batch = {
            "patch_embeds": jax.random.normal(key, (b, cfg.num_patches, cfg.d_model),
                                              jnp.dtype(cfg.param_dtype)),
            "tokens": jax.random.randint(key, (b, args.prompt_len - cfg.num_patches),
                                         0, cfg.vocab_size),
        }
    else:
        batch = {"tokens": jax.random.randint(key, (b, args.prompt_len), 0, cfg.vocab_size)}

    par = ParallelContext(mesh=None) if args.host_kv_chunks else None
    t0 = time.perf_counter()
    logits, cache = SV.prefill_step(cfg, par, params, batch, max_len=max_len)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill {args.prompt_len} tokens x {b} seqs: {t_prefill*1e3:.1f} ms")

    sampling = DL.SamplingConfig(temperature=args.temperature, top_k=args.top_k)
    key, sub = jax.random.split(key)
    tok0 = DL.sample_token(logits[:, : cfg.vocab_size], sub, sampling)
    steps = args.gen - 1

    if args.per_token or cfg.frontend == "audio_frames":
        decode = jax.jit(
            lambda cache, inp, pos: SV.decode_step(
                cfg, par, params, cache, inp, pos, n_host_chunks=args.host_kv_chunks)
        )
        outs = [tok0[:, None]]
        t0 = time.perf_counter()
        for i in range(steps):
            inp = ({"tokens": outs[-1]} if cfg.frontend != "audio_frames"
                   else {"frame_embeds": jax.random.normal(key, (b, 1, cfg.d_model),
                                                           jnp.dtype(cfg.param_dtype))})
            logits, cache = decode(cache, inp, jnp.int32(args.prompt_len + i))
            key, sub = jax.random.split(key)
            outs.append(DL.sample_token(logits[:, : cfg.vocab_size], sub, sampling)[:, None])
        jax.block_until_ready(outs[-1])
        seqs = jnp.concatenate(outs, axis=1)
        mode = "per-token loop"
    else:
        decode = jax.jit(lambda cache, tok, pos, key: DL.decode_tokens(
            cfg, par, params, cache, tok, pos, num_steps=steps,
            n_host_chunks=args.host_kv_chunks, sampling=sampling, key=key))
        key, sub = jax.random.split(key)
        toks, _ = decode(cache, tok0[:, None], jnp.full((b,), args.prompt_len, jnp.int32), sub)
        jax.block_until_ready(toks)  # includes compile; timed run below
        t0 = time.perf_counter()
        toks, _ = decode(cache, tok0[:, None], jnp.full((b,), args.prompt_len, jnp.int32), sub)
        jax.block_until_ready(toks)
        seqs = jnp.concatenate([tok0[:, None], toks], axis=1)
        mode = "scan"
    dt = time.perf_counter() - t0
    print(f"decode [{mode}] {steps} steps x {b} seqs: {dt*1e3:.1f} ms "
          f"({dt / max(1, steps) * 1e3:.2f} ms/step, "
          f"{steps * b / dt:.1f} tok/s)")
    print("generated token ids (first seq):", seqs[0].tolist())


if __name__ == "__main__":
    main()
