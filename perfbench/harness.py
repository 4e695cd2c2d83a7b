"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json``, its configuration file, ``traffic/<traffic>.json``,
``limits/<cell>.json``, and a reader ``metrics/<metric>.py`` per per-layer
metric.  A new cell or metric adds files and entries; this file stays.

Set-up builds the program's compiled step with its state from the seed and
drives it through the checked steps (the same call and feed as the window,
each step on new rows).  The window then runs steps back to back while fewer
than ``seconds`` have passed and ends when the last step started in it
completes.  After the window the program's state is freed and the plain
reference (``reference.py``) follows the checked steps from the same seed;
``check.py`` compares the two.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from perfbench import check, traffic as TR, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = ".jax_cache"  # under the checkout's root, fixed: its path keys the cache
GIB = 2 ** 30
TOP = 10  # entries of each breakdown list


class CellError(RuntimeError):
    """The run cannot measure this cell here (no result is printed)."""


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell's entry, configuration, traffic, limits and per-layer metrics."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    data = os.path.join(root, bench["paths"][0])
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    per_layer = [m for m in bench["per_layer"] if workload in m.get("workloads", [workload])]
    return {"cell": cell, "model": _json(os.path.join(root, config["file"])),
            "traffic": _json(os.path.join(data, "traffic", cell["traffic"] + ".json")),
            "limits": _json(os.path.join(data, "limits", workload + ".json")),
            "per_layer": per_layer}


def reader(name: str):
    """The ``read(reading)`` function of ``metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Reading:
    """What a per-layer metric reader may read from a traced run."""

    def __init__(self, spec, peak, chips, steps, window_s, trace):
        self.model, self.traffic = spec["model"], spec["traffic"]
        self.peak, self.chips, self.steps, self.window_s = peak, chips, steps, window_s
        self.trace = trace


def _compile_counter():
    """A list that grows by one name per backend compile (or cache load)."""
    import jax

    seen = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"JAX's first device is {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise CellError(f"the cell needs {chips} chips and {len(devs)} devices are visible")
    return devs[:chips]


def _plan_peak_bytes(compiled) -> int:
    """The step's peak device memory per device, as XLA's buffer assignment
    plans it.  The runtime reserves a program's temporaries when it loads the
    program, outside the allocator's ``peak_bytes_in_use``, which therefore
    misses them (``probe_hbm.py``)."""
    return compiled.memory_analysis().peak_memory_in_bytes


def configure_jax(root: str) -> None:
    """JAX's persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def stepper(tr, traffic: dict, vocab: int, seed: int):
    """one_step(i): put step i's batch, dispatch the step, block on its loss."""
    import jax

    def one_step(i):
        with jax.profiler.TraceAnnotation("bench.feed"):
            db = tr.put(TR.batch(traffic, vocab, seed, i))
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            metrics = tr.call(db)
        with jax.profiler.TraceAnnotation("bench.block"):
            return float(jax.block_until_ready(metrics["loss"]))

    return one_step


def checked_steps(tr, one_step, steps: int):
    """The program's readings over the first ``steps`` steps, and its
    parameters after them (on the host)."""
    import jax

    from perfbench import reference

    prog = {"losses": []}
    for i in range(steps):
        prog["losses"].append(one_step(i))
        if i == 0:
            prog["grad_norms"] = [float(x) for x in reference.leaf_norms(tr.opt_state.m)]
    return prog, jax.device_get(tr.params)


def reference_readings(model: dict, traffic: dict, seed: int, steps: int, devices,
                       mode: str = "fp32"):
    """The reference's readings over the same steps on the cell's devices,
    and the seed's weights."""
    from perfbench import reference, weights

    batches = [TR.batch(traffic, model["vocab_size"], seed, i) for i in range(steps)]
    ref = reference.trajectory(model, batches, seed, mode, devices)
    p0 = weights.make(model, seed, out_shardings=reference.shardings(model, devices))
    ref["update_norms"] = [float(x) for x in reference.change_norms(ref.pop("params"), p0)]
    return ref, p0


def update_norms(params_host, p0) -> list:
    """Each leaf's change from ``p0`` of the program's parameters (on the host),
    put where ``p0`` lies."""
    import jax

    from perfbench import reference

    where = jax.tree.map(lambda w: w.sharding, p0)
    return [float(x) for x in reference.change_norms(jax.device_put(params_host, where), p0)]


def run(root: str, workload: str, seed: int, seconds: float, trace: bool, t0: float,
        require_tpu: bool = True) -> dict:
    """One run; returns the result line's object (``correct`` and the rest)."""
    spec = load_cell(root, workload)
    model, traffic, cell = spec["model"], spec["traffic"], spec["cell"]
    devices = _devices(cell["chips"], require_tpu)
    import jax

    from perfbench import program, reference

    kind = devices[0].device_kind
    peaks = _json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks and (require_tpu or trace):
        raise CellError(f"no peaks for device kind {kind!r} in peaks.json")
    configure_jax(root)
    compiles = _compile_counter()
    steps_checked = spec["limits"]["steps"]

    marks = [("start", t0), ("imports", time.perf_counter())]
    tr = program.Trainer(model, traffic, seed, devices)
    marks.append(("weights", time.perf_counter()))
    tr.compile(TR.batch(traffic, model["vocab_size"], seed, 0))
    marks.append(("compile", time.perf_counter()))
    one_step = stepper(tr, traffic, model["vocab_size"], seed)
    prog, snapshot = checked_steps(tr, one_step, steps_checked)
    marks.append(("checked steps", time.perf_counter()))

    tdir = tempfile.mkdtemp(prefix="perfbench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    n_setup_compiles = len(compiles)
    window_losses = []
    t_w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t_w0 < seconds:
            window_losses.append(one_step(steps_checked + len(window_losses)))
        t_w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    in_window = len(compiles) - n_setup_compiles
    peak = _plan_peak_bytes(tr.compiled)
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    n = len(window_losses)
    marks.append(("window start", t_w0))
    print(f"[perfbench] {workload} seed {seed}: set-up {t_w0 - t0:.3f} s ("
          + ", ".join(f"{b[0]} {b[1] - a[1]:.1f}" for a, b in zip(marks, marks[1:]))
          + f"), window {t_w1 - t_w0:.3f} s, {n} steps, compiles in window {in_window}",
          flush=True)
    print(f"[perfbench] step's planned peak {peak / GIB:.3f} GiB per device; allocator's "
          f"peak_bytes_in_use {in_use / GIB:.3f} GiB", flush=True)
    print(f"[perfbench] losses: checked {prog['losses']}, window {window_losses}", flush=True)
    if in_window:
        raise CellError(f"{in_window} compiles inside the window: {compiles[n_setup_compiles:]}")

    result = {"correct": False, "attempted": n,
              "failed": sum(not math.isfinite(x) for x in window_losses), "metrics": {},
              "device": {"platform": devices[0].platform, "kind": kind, "count": len(devices),
                         "memory_peak_bytes": peak}}
    if trace:
        tr_data = tracing.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        reading = Reading(spec, peaks[kind], len(devices), n, t_w1 - t_w0, tr_data)
        for m in spec["per_layer"]:
            v = reader(m["name"])(reading)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = tracing.busy_s(tr_data)
        result["device"]["window_s"] = tracing.window_s(tr_data)
        result["breakdown"] = breakdown(tr_data)
    else:
        tokens = traffic["batch"] * traffic["seq"]
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens * n / (t_w1 - t_w0), "unit": "tokens/s"},
            "peak_hbm_gib": {"value": peak / GIB, "unit": "GiB"},
            "setup_s": {"value": t_w0 - t0, "unit": "s"},
        }

    # the check, once the program's state is freed
    tr.free()
    del tr
    gc.collect()
    t_r = time.perf_counter()
    ref, p0 = reference_readings(model, traffic, seed, steps_checked, devices)
    prog["update_norms"] = update_norms(snapshot, p0)
    names = reference.leaf_names(snapshot)
    del snapshot, p0
    nums = check.numbers(prog, ref)
    ok, shown = check.judge(nums, spec["limits"])
    print(f"[perfbench] reference {time.perf_counter() - t_r:.1f} s; losses {ref['losses']}; "
          f"worst grad leaf {names[nums['worst_grad_leaf']]}, worst update leaf "
          f"{names[nums['worst_update_leaf']]}", flush=True)
    result["correct"] = ok and result["failed"] == 0 and all(
        math.isfinite(x) for x in prog["losses"])
    result["checked"] = shown
    for k, v in shown.items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr, flush=True)
    return result


def breakdown(trace: tracing.Trace) -> dict:
    """The kinds of device op that took most time (seconds per device; waits
    on async ops included) and the longest gaps between compute ops on the
    first device, named by the host span and the device wait around them."""
    lo, hi = trace.window
    per_kind = {}
    for ops in trace.devices.values():
        for o in ops:
            if o.opcode not in tracing.CONTROL_FLOW and o.end > lo and o.start < hi:
                k = tracing.kind(o)
                per_kind[k] = per_kind.get(k, 0.0) + min(o.end, hi) - max(o.start, lo)
    top = sorted(per_kind.items(), key=lambda kv: -kv[1])[:TOP]
    plane = sorted(trace.devices)[0]
    busy = tracing.union(tracing.window_ops(trace, plane))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])[:TOP]
    spans = [s for s in trace.spans if s[0] != tracing.SPAN_PREFIX + "window"]
    waits = [o for o in trace.devices[plane] if o.opcode.endswith("-done")]

    def name(mid):
        host = [n for n, s, e in spans if s <= mid < e]
        dev = [tracing.kind(o) for o in waits if o.start <= mid < o.end]
        return (host[-1] if host else "no bench span") + " | " + (dev[-1] if dev else "no wait")

    return {"device_ops": [[k, v / len(trace.devices) / 1e9] for k, v in top],
            "idle_gaps": [[name((s + e) / 2), (e - s) / 1e9] for s, e in gaps]}
