"""Reduction of a profiler trace to intervals, and interval arithmetic.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU
each device plane holds two lines the benchmark reads:

  * ``XLA Ops``: every HLO instruction that ran, named by its HLO text
    (``%fusion.12 = bf16[...] fusion(...)``).  Control-flow instructions
    (``while``, ``conditional``, ``call``) span their bodies' ops, and
    ``*-done`` instructions span the device's wait for an async op;
  * ``Async XLA Ops``: each async op from its start to its done, the
    interval in which it is in flight.

An op's ``opcode`` is read from its HLO text.  A compute op is a leaf
instruction of ``XLA Ops`` that is neither control flow nor the start or
done of an async op.  A collective is an all-to-all, all-gather,
reduce-scatter, all-reduce or collective-permute, read from the opcode
without ``-start``/``-done``, or, where XLA wraps it in a generic
``async-start`` or a fusion, from the instruction's name; on a TPU an
asynchronous collective is a pair of fusions in ``XLA Ops``,
``%async-collective-start.N`` and ``%async-collective-done.N``.  A host
transfer is an async op with an operand or a result in pinned host memory
(memory space ``S(5)`` in its HLO text); the host calls that XLA's host
offloading runs for stores kept there count too.
The benchmark's own host spans (``bench.*``, from
``jax.profiler.TraceAnnotation``) say what the host was doing; the one
``bench.window`` span is the measured window.  Intervals are (start_ns,
end_ns) on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
SPAN_PREFIX = "bench."
CONTROL_FLOW = frozenset({"while", "conditional", "call"})
HOST_MEMORY = "S(5)"
COLLECTIVES = ("all-to-all", "all-gather", "reduce-scatter", "all-reduce", "collective-permute")
_WRAPPED_COLLECTIVES = COLLECTIVES + ("async-collective",)
_OPCODE = re.compile(r"[\]\}\)]\s([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction's name, e.g. "%fusion.12"
    opcode: str
    start: float
    end: float
    text: str  # the event's full name and its string stats, for matching rules


def op(text: str, start: float, end: float) -> Op:
    name, _, rhs = text.partition(" = ")
    m = _OPCODE.search(rhs)
    return Op(name.split(" ")[0], m.group(1) if m else "", start, end, text)


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> [Op] of its XLA Ops line, by start
    asyncs: dict  # plane name -> [Op] of its Async XLA Ops line, by start
    spans: list  # [(name, start, end)] of the benchmark's host spans
    window: tuple  # (start, end) of the bench.window span


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    devices, asyncs, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            for line, into in ((OPS_LINE, devices), (ASYNC_LINE, asyncs)):
                evs = lines[line].events if line in lines else []
                into[plane.name] = sorted(
                    (op(" ".join([e.name] + [v for _, v in e.stats if isinstance(v, str)]),
                        e.start_ns, e.start_ns + e.duration_ns) for e in evs),
                    key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    wins = [(s, e) for n, s, e in spans if n == SPAN_PREFIX + "window"]
    if not devices or len(wins) != 1:
        raise RuntimeError(f"trace holds {len(devices)} TPU planes and {len(wins)} window spans")
    return Trace(devices, asyncs, sorted(spans, key=lambda s: s[1]), wins[0])


# --------------------------------------------------------------------------- ops


def is_compute(o: Op) -> bool:
    return not (o.opcode in CONTROL_FLOW or o.opcode.endswith(("-start", "-done")))


def is_collective(o: Op) -> bool:
    base = re.sub(r"-(start|done)$", "", o.opcode)
    return base in COLLECTIVES or (base in ("async", "fusion")
                                   and o.name.lstrip("%").startswith(_WRAPPED_COLLECTIVES))


def is_host_transfer(o: Op) -> bool:
    return HOST_MEMORY in o.text


def kind(o: Op) -> str:
    """A short name shared by the instances of one kind of op."""
    if o.opcode == "custom-call":
        m = _TARGET.search(o.text)
        return "custom-call:" + (m.group(1) if m else "?")
    base = re.sub(r"\.\d+", "", o.name.lstrip("%"))
    return base if base.startswith(o.opcode.split("-")[0]) else f"{o.opcode}:{base}"


# --------------------------------------------------------------------------- intervals


def union(intervals) -> list:
    """Sorted disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def uncovered(a, b) -> float:
    """Length of the points of ``a`` that no interval of ``b`` covers."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def window_ops(trace: Trace, plane: str, match=is_compute, line: str = OPS_LINE) -> list:
    """Intervals of the plane's ops that ``match`` takes, clipped to the window."""
    ops = trace.devices if line == OPS_LINE else trace.asyncs
    return clip([(o.start, o.end) for o in ops[plane] if match(o)], *trace.window)


def busy_s(trace: Trace) -> float:
    """Seconds in which a compute op ran, averaged over the devices."""
    return sum(length(window_ops(trace, p)) for p in trace.devices) / len(trace.devices) / 1e9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9
