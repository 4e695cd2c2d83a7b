"""Unified telemetry layer (`runtime/telemetry.py`): registry primitives
stay bounded, the `last_stats` facade keeps the old dict contract while
mirroring scalars into the registry, exporters emit valid Chrome
trace-event JSON / Prometheus text, and — the golden acceptance — the
same seed (plus the same fault plan) reproduces the IDENTICAL lifecycle
event sequence on the deterministic step clock, wall-clock excluded:

* registry/StepRing/StatsView/timed_dispatch unit behavior (no jax);
* per-request summaries reconstructed from synthetic lifecycle events;
* compile counting through the `per_engine` jit wrapper, including the
  bounded-program-set alert when a program recompiles past its limit;
* router failover telemetry on fake replicas: per-call vs lifetime
  counter views (the regression `test_failover_per_call_vs_lifetime`
  referenced from `launch/router.py`) and the pinned
  retry -> death -> recover -> re-home event order, byte-identical
  across two runs of the same scripted fault;
* the SLO engine golden: two fresh engines, same seed, identical
  deterministic trace views through admit/preempt/resume/emit.
"""
import dataclasses
import functools
import json

import pytest

from repro.runtime import telemetry as TM


# ---------------------------------------------------------------------------
# registry primitives (no jax)
# ---------------------------------------------------------------------------


def test_registry_create_on_first_use_and_value():
    reg = TM.MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    reg.counter("a").inc(3)
    reg.gauge("g").set(2.5)
    reg.histogram("h").observe(1.0)
    assert reg.value("a") == 3
    assert reg.value("g") == 2.5
    assert reg.value("missing", default=-1) == -1
    snap = reg.snapshot()
    assert snap["a"] == 3 and snap["h"]["count"] == 1


def test_histogram_exact_aggregates_bounded_reservoir():
    h = TM.Histogram(reservoir=8)
    for v in range(100):
        h.observe(float(v))
    # aggregates are exact over the full stream ...
    assert h.count == 100 and h.total == sum(range(100))
    assert h.vmin == 0.0 and h.vmax == 99.0
    # ... percentiles over the most recent window only, drops counted
    assert len(h.window) == 8 and h.dropped == 92
    assert h.percentile(50) >= 92.0
    s = h.summary()
    assert s["count"] == 100 and s["dropped"] == 92


def test_step_ring_bounds_like_a_list():
    ring = TM.StepRing(cap=4)
    for i in range(6):
        ring.append({"ms": float(i)})
    assert len(ring) == 4 and ring.dropped == 2
    assert ring[0]["ms"] == 2.0 and ring[-1]["ms"] == 5.0
    assert [r["ms"] for r in ring[1:]] == [3.0, 4.0, 5.0]  # slicing
    assert [r["ms"] for r in ring] == [2.0, 3.0, 4.0, 5.0]  # iteration
    assert bool(ring) and not bool(TM.StepRing())


def test_stats_view_mirrors_scalars_into_registry():
    tel = TM.Telemetry(component="t")
    st = tel.stats_view({"dispatches": 0, "policy": "slo", "radix": True})
    st["dispatches"] += 3
    st["prefix_hit_tokens"] = 7
    # dict contract intact for existing consumers
    assert st["dispatches"] == 3 and st.get("missing", 5) == 5
    assert "policy" in st and dict(st)["prefix_hit_tokens"] == 7
    # scalars live in the registry (single source of truth for BENCH) ...
    assert tel.registry.value("dispatches") == 3
    assert tel.registry.value("prefix_hit_tokens") == 7
    # ... but strings/bools/lists stay local (BENCH values must be numeric)
    assert "policy" not in tel.registry.gauges
    assert "radix" not in tel.registry.gauges
    assert st["radix"] is True


def test_timed_dispatch_record_shape_and_registry():
    tel = TM.Telemetry(component="t")
    stats = tel.stats_view({"steps": tel.steps_ring(), "dispatches": 0})
    with TM.timed_dispatch(tel, stats, prefilling=1) as td:
        td.emitted = 4
    with TM.timed_dispatch(tel, stats, step=9) as td:
        td.emitted = 2
        td.prefilling = 3
    assert stats["dispatches"] == 2
    rec0, rec1 = stats["steps"][0], stats["steps"][1]
    assert set(rec0) == {"ms", "prefilling", "emitted"}
    assert rec0["prefilling"] == 1 and rec0["emitted"] == 4
    assert rec1["step"] == 9 and rec1["prefilling"] == 3
    assert tel.registry.value("emitted_tokens") == 6
    assert tel.registry.histograms["dispatch_ms"].count == 2
    assert tel.tracer.kinds() == ["engine.dispatch", "engine.dispatch"]


def test_tracer_deterministic_view_excludes_wall_clock():
    t = TM.Tracer()
    t.event("x", step=1, request=0, dur_ms=3.5, lat_ms=9.9, n=2)
    (ev,) = t.deterministic_view()
    assert ev == ("x", 1, 0, None, None, None, (("n", 2),))
    flat = repr(ev)
    assert "3.5" not in flat and "9.9" not in flat


def test_tracer_buffer_bounded():
    t = TM.Tracer(max_events=3)
    for i in range(5):
        t.event("e", step=i)
    assert len(t.events) == 3 and t.dropped == 2
    assert [e["step"] for e in t.events] == [2, 3, 4]


def test_set_tracing_off_stops_events_not_counters():
    tel = TM.Telemetry(component="t").set_tracing(False)
    tel.event("request.admit", request=0)
    tel.compile_event("segment")
    assert len(tel.tracer.events) == 0
    assert tel.registry.value("compiles_segment") == 1  # still counted


def test_compile_event_alert_past_program_limit():
    tel = TM.Telemetry(component="t", program_limit=1)
    tel.compile_event("segment")
    assert tel.alerts() == 0
    tel.compile_event("segment")  # second compile of the same program
    assert tel.alerts() == 1
    assert "alert.programs" in tel.tracer.kinds()


def test_request_summaries_from_synthetic_events():
    t = TM.Tracer()
    t.event("request.queued", request=0, session="s", step=2)
    t.event("request.admit", request=0, step=5, prefix_hit=8)
    t.event("request.emit", request=0, step=7, n=2)
    t.event("request.preempt", request=0, step=8)
    t.event("request.resume", request=0, step=10, prefix_hit=4)
    t.event("request.emit", request=0, step=11, n=1)
    t.event("request.emit", request=0, step=12, n=1)
    s = TM.request_summaries(t)[0]
    assert s["queued_step"] == 2 and s["admit_step"] == 5
    assert s["queue_wait"] == 3
    assert s["ttft"] == 5 and s["first_emit"] == 7 and s["last_emit"] == 12
    assert s["n_emitted"] == 4 and s["preemptions"] == 1
    assert s["prefix_hit_tokens"] == 12
    assert s["itl_p50"] == 1 and s["max_gap"] == 4


# ---------------------------------------------------------------------------
# exporters (no jax)
# ---------------------------------------------------------------------------


def _sample_telemetry():
    tel = TM.Telemetry(component="engine", replica=1)
    tel.registry.counter("emitted_tokens").inc(10)
    tel.registry.gauge("capacity").set(4)
    tel.registry.histogram("dispatch_ms").observe(2.0)
    tel.event("request.admit", request=0, slot=1, step=3)
    tel.event("engine.dispatch", step=4, dur_ms=2.0)
    return tel


def test_chrome_trace_round_trips_as_json():
    doc = json.loads(json.dumps(TM.chrome_trace([_sample_telemetry()])))
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"process_name", "thread_name",
            "request.admit", "engine.dispatch"} <= names
    meta = [e for e in evs if e["name"] == "process_name"]
    assert meta[0]["args"]["name"] == "engine[1]"  # replica-labeled pid
    span = next(e for e in evs if e["name"] == "engine.dispatch")
    assert span["ph"] == "X" and span["dur"] == pytest.approx(2000.0)
    inst = next(e for e in evs if e["name"] == "request.admit")
    assert inst["ph"] == "i" and inst["tid"] == 2  # slot 1 -> track 2
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e["name"] == "thread_name"}
    assert tracks[0] == "scheduler" and tracks[2] == "slot 1"


def test_prometheus_text_exposition():
    text = TM.prometheus_text([_sample_telemetry()])
    assert '# TYPE repro_emitted_tokens counter' in text
    assert ('repro_emitted_tokens{component="engine",replica="1"} 10'
            in text)
    assert '# TYPE repro_capacity gauge' in text
    assert 'repro_dispatch_ms{component="engine",replica="1",quantile="0.5"}' \
        in text
    assert 'repro_dispatch_ms_count{component="engine",replica="1"} 1' in text
    # every sample line parses as 'name{labels} value'
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, rest = line.split("{", 1)
        labels, value = rest.rsplit("} ", 1)
        assert name.startswith("repro_") and float(value) is not None


def test_write_exporters(tmp_path):
    tel = _sample_telemetry()
    TM.write_chrome_trace(str(tmp_path / "t.json"), tel)
    TM.write_prometheus(str(tmp_path / "m.prom"), tel)
    assert json.load(open(tmp_path / "t.json"))["traceEvents"]
    assert "repro_" in open(tmp_path / "m.prom").read()


# ---------------------------------------------------------------------------
# router failover telemetry on fakes (no jax)
# ---------------------------------------------------------------------------


class StoreEcho:
    """Echo replica with a fake (but file-backed) prefix-cache store, so
    SharedKVStore's publish/recover path runs for real."""

    def __init__(self):
        self.last_stats = {"prompt_tokens": 0, "prefix_hit_tokens": 0}

    def generate(self, prompts):
        toks = [list(getattr(p, "tokens", p)) for p in prompts]
        self.last_stats = {"prompt_tokens": sum(len(t) for t in toks),
                           "prefix_hit_tokens": 0}
        return [[t[0], len(t)] for t in toks]

    def save_kv_store(self, path):
        with open(path, "w") as f:
            f.write("pages")
        return 3

    def restore_kv_store(self, path):
        return 3


def quiet(msg):
    pass


def _crash_router(tmp_path, fault_kind="raise", max_retries=1):
    from repro.launch.faults import Fault, FaultyReplica
    from repro.launch.kvstore import SharedKVStore
    from repro.launch.router import ReplicaRouter

    prompts = [[i, i + 1, i + 2] for i in range(8)]
    store = SharedKVStore(str(tmp_path))
    reps = [FaultyReplica(StoreEcho()) for _ in range(2)]
    rt = ReplicaRouter(reps, max_retries=max_retries, kv_store=store,
                       warn=quiet)
    victim = rt.home_of(prompts[0])
    reps[victim].faults.append(Fault(fault_kind, 0))
    return rt, store, prompts, victim


ROUTER_LIFECYCLE = {"router.retry", "router.death", "router.recover",
                    "router.rehome", "router.rejoin"}


def test_failover_trace_golden_identical_and_pinned_order(tmp_path):
    """Same fault plan, two fresh routers: identical deterministic views,
    and the failover events land in the pinned order
    retry -> death -> recover -> re-home (one re-home per orphaned
    request)."""
    views, kvviews = [], []
    for run in range(2):
        rt, store, prompts, victim = _crash_router(tmp_path / str(run))
        outs = rt.generate(prompts)
        assert all(len(o) == 2 for o in outs)
        views.append(rt.telemetry.tracer.deterministic_view())
        kvviews.append(store.telemetry.tracer.deterministic_view())
        kinds = [k for k in rt.telemetry.tracer.kinds()
                 if k in ROUTER_LIFECYCLE]
        n_rehomed = rt.last_stats["failover"]["rehomed_requests"]
        assert n_rehomed > 0
        assert kinds == (["router.retry", "router.death", "router.recover"]
                         + ["router.rehome"] * n_rehomed)
        assert {"kvstore.publish", "kvstore.recover"} <= \
            set(store.telemetry.tracer.kinds())
    assert views[0] == views[1], "router trace must be seed-deterministic"
    assert kvviews[0] == kvviews[1]


def test_rejoin_emits_recovery_event(tmp_path):
    rt, store, prompts, victim = _crash_router(tmp_path)
    rt.generate(prompts)
    rt.replicas[victim].heal()
    restored = rt.rejoin(victim)
    assert restored == 3  # StoreEcho's own published file reloads
    ev = next(e for e in rt.telemetry.tracer.events
              if e["kind"] == "router.rejoin")
    assert ev["replica"] == victim and ev["args"]["pages"] == 3
    assert "kvstore.restore_self" in store.telemetry.tracer.kinds()


def test_failover_per_call_vs_lifetime(tmp_path):
    """Satellite 6 regression: `last_stats["failover"]` counters are
    PER-CALL deltas (existing consumers rely on that); the lifetime
    totals live in `failover["lifetime"]` and in the registry's
    `router_*` counters, while the `failover_*` gauges mirror the last
    call's deltas."""
    from repro.launch.faults import Fault, FaultyReplica
    from repro.launch.router import ReplicaRouter

    prompts = [[i, i + 1] for i in range(6)]
    reps = [FaultyReplica(StoreEcho()) for _ in range(2)]
    rt = ReplicaRouter(reps, max_retries=2, warn=quiet)
    victim = rt.home_of(prompts[0])
    reps[victim].faults.append(Fault("transient", 0))  # one-shot fault

    rt.generate(prompts)
    fo1 = rt.last_stats["failover"]
    assert fo1["retries"] == 1 and fo1["deaths"] == 0
    assert fo1["lifetime"]["retries"] == 1

    rt.generate(prompts)  # clean second call
    fo2 = rt.last_stats["failover"]
    assert fo2["retries"] == 0, "per-call view must reset between calls"
    assert fo2["lifetime"]["retries"] == 1, "lifetime view must not"
    reg = rt.telemetry.registry
    assert reg.value("router_retries") == 1          # lifetime counter
    assert reg.value("failover_retries") == 0        # last-call gauge


def test_router_dispatch_spans_on_step_clock():
    from repro.launch.router import ReplicaRouter

    rt = ReplicaRouter([StoreEcho(), StoreEcho()], warn=quiet)
    rt.generate([[1, 2], [3, 4], [5, 6]])
    spans = [e for e in rt.telemetry.tracer.events
             if e["kind"] == "router.dispatch"]
    assert spans and all(e["dur_ms"] is not None for e in spans)
    assert [e["step"] for e in spans] == \
        list(range(1, len(spans) + 1))  # monotone dispatch-seq clock


# ---------------------------------------------------------------------------
# engine golden: same seed => identical deterministic trace (jax)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _setup():
    import jax

    from repro.configs import get_config, reduced
    from repro.models import transformer as T

    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")),
                              param_dtype="float32", remat="none")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _slo_run():
    """A tiny preempting SLO workload on a fresh engine; returns the
    engine after one generate."""
    import numpy as np

    from repro.runtime import decode_loop as DL
    from repro.runtime import paged as PG

    cfg, params = _setup()
    rng = np.random.default_rng(0)
    V = cfg.vocab_size
    long_p = tuple(int(t) for t in rng.integers(0, V, 13))
    short_p = tuple(int(t) for t in rng.integers(0, V, 5))
    eng = PG.SLOPagedServeEngine(cfg, params, slots=1, bucket=16,
                                 max_new_tokens=8, page_size=4, segment=1,
                                 spill_pages=8)
    outs = eng.generate([
        DL.Request(tokens=long_p, priority=1, arrival=0, session="batch"),
        DL.Request(tokens=short_p, priority=0, arrival=6, session="chat")])
    return eng, outs


def test_slo_trace_golden_deterministic():
    """THE tentpole golden: two fresh engines, same seed, byte-identical
    deterministic trace views through a preempt/resume cycle — and the
    trace carries the full lifecycle taxonomy."""
    eng1, outs1 = _slo_run()
    eng2, outs2 = _slo_run()
    assert outs1 == outs2
    v1 = eng1.telemetry.tracer.deterministic_view()
    v2 = eng2.telemetry.tracer.deterministic_view()
    assert v1 == v2, "same seed must reproduce the identical trace"
    kinds = set(eng1.telemetry.tracer.kinds())
    assert {"request.queued", "request.admit", "request.preempt",
            "request.resume", "request.emit", "request.complete",
            "engine.dispatch", "compile.segment"} <= kinds
    # trace-derived summaries agree with the scheduler's own accounting
    summ = eng1.telemetry.request_summaries()
    st = eng1.last_stats
    for ridx, rs in enumerate(st["requests"]):
        assert summ[ridx]["n_emitted"] == rs["n_emitted"]
        assert summ[ridx]["preemptions"] == rs["preemptions"]
        assert summ[ridx]["first_emit"] == rs["first_emit"]
    assert sum(s["preemptions"] for s in summ.values()) == st["preemptions"]


def test_compile_counters_match_program_cache():
    """The per_engine wrapper's compile events count exactly what the
    jit caches hold: registry compiles_* == compiled_programs(), and a
    clean run raises no bounded-program-set alert."""
    eng, _ = _slo_run()
    progs = eng.compiled_programs()
    for name, cached in progs.items():
        assert eng.telemetry.registry.value(f"compiles_{name}") == cached, \
            name
    assert eng.telemetry.alerts() == 0


def test_train_loop_counts_compiles_and_profiles_steps(tmp_path):
    """Three steps of a jitted train step compile one program
    (``compiles_train_step``, no alert), and a ``jax.profiler`` trace holds
    each step's ``train`` annotation with its step number around the
    ``train.feed``/``train.dispatch``/``train.block`` spans."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.configs import ShapeConfig, get_config, reduced
    from repro.data.pipeline import CheckpointableIterator, make_batch_fn
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.runtime.train_loop import TrainConfig, TrainLoop, make_train_step

    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), num_layers=1)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    oc = adamw.OptConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    tc = TrainConfig(steps=3, log_every=100)
    loop = TrainLoop(cfg, None, oc, tc, jax.jit(make_train_step(cfg, None, oc, tc)),
                     CheckpointableIterator(make_batch_fn(cfg, ShapeConfig("t", 32, 2, "train"))))
    with jax.profiler.trace(str(tmp_path)):
        loop.run(params, adamw.init(oc, params),
                 put_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()})
    assert loop.telemetry.registry.value("compiles_train_step") == 1
    assert loop.telemetry.alerts() == 0
    assert [h["step"] for h in loop.history] == [1, 2, 3]

    pd = ProfileData.from_file(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1])
    events = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                     for p in pd.planes if p.name.startswith("/host:")
                     for ln in p.lines for e in ln.events
                     if e.name == "train" or e.name.startswith("train.")), key=lambda e: e[0])
    steps = [e for e in events if e[2] == "train"]
    assert [e[3].get("step_num") for e in steps] == [1, 2, 3]
    for s, t, _, _ in steps:
        inside = [e[2] for e in events if s <= e[0] and e[1] <= t and e[2] != "train"]
        assert inside == ["train.feed", "train.dispatch", "train.block"]


def test_train_loop_gauges_fpdt_forward_fetches():
    """A u=4 FPDT train run sets ``fpdt_fwd_kv_fetches`` to the helper's
    count when it sees its first batch: the grouped forward fetches KV
    chunks 0 and 1 once, for query chunks 2 and 3."""
    import jax
    import jax.numpy as jnp

    from repro.configs import ShapeConfig, get_config, reduced
    from repro.core import fpdt
    from repro.data.pipeline import CheckpointableIterator, make_batch_fn
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.runtime.train_loop import TrainConfig, TrainLoop, make_train_step

    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), num_layers=1,
                              fpdt_chunks=4, fpdt_offload=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    oc = adamw.OptConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    tc = TrainConfig(steps=1, log_every=100)
    loop = TrainLoop(cfg, None, oc, tc, jax.jit(make_train_step(cfg, None, oc, tc)),
                     CheckpointableIterator(make_batch_fn(cfg, ShapeConfig("t", 32, 2, "train"))))
    assert loop.telemetry.registry.value("fpdt_fwd_kv_fetches", None) is None
    loop.run(params, adamw.init(oc, params),
             put_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()})
    assert loop.telemetry.registry.value("fpdt_fwd_kv_fetches") == \
        fpdt.fwd_kv_fetches(4, cq=8) == 2
    assert loop.telemetry.registry.value("fpdt_fwd_kv_fetches_overlapped") == 1
    assert loop.telemetry.registry.value("fpdt_bwd_q_fetches_overlapped") == 6


@pytest.mark.parametrize("sparsity,impl,offload,want", [
    (0.0, "pallas", True, (56, 49, 120)), (0.5, "pallas", True, (56, 0, 0)),
    (0.0, "xla_flash", True, (56, 0, 0)), (0.0, "pallas", False, (56, 0, 0))])
def test_train_loop_gauges_fpdt_overlapped_fetches(sparsity, impl, offload, want):
    """The 64k cell's schedule, u=16: the first batch sets
    ``fpdt_fwd_kv_fetches_overlapped`` and ``fpdt_bwd_q_fetches_overlapped``
    to 49 and 120 on a dense offloaded schedule with the Pallas kernels
    (two head groups), and to 0 on a sparse one, with the XLA kernels (no
    head loop) and without offload."""
    import jax.numpy as jnp

    from repro.configs import ShapeConfig, get_config, reduced
    from repro.core.parallel import ParallelContext
    from repro.data.pipeline import CheckpointableIterator, make_batch_fn
    from repro.optim import adamw
    from repro.runtime.train_loop import TrainConfig, TrainLoop

    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), num_layers=1,
                              fpdt_chunks=16, fpdt_offload=offload, attn_sparsity=sparsity)
    loop = TrainLoop(cfg, ParallelContext(mesh=None, attn_impl=impl), adamw.OptConfig(),
                     TrainConfig(steps=1), None,
                     CheckpointableIterator(make_batch_fn(cfg, ShapeConfig("t", 64, 1, "train"))))
    loop._record_schedule({"tokens": jnp.zeros((1, 16 * 4096), jnp.int32)})
    value = loop.telemetry.registry.value
    assert (value("fpdt_fwd_kv_fetches"), value("fpdt_fwd_kv_fetches_overlapped"),
            value("fpdt_bwd_q_fetches_overlapped")) == want
