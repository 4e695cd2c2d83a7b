"""collective_exposed_pct: share of the traced window in which a collective
runs on a device and no other compute op does, the worst device's.

A collective (``tracing.is_collective``) runs while a synchronous one is in
``XLA Ops`` or an asynchronous one is in flight in ``Async XLA Ops``; other
compute is ``tracing.is_compute`` less the collectives.  Ops are told apart
by opcode, whatever code issued them.  A trace with no collective reads
nothing.
"""
from perfbench import tracing


def _sync_collective(o) -> bool:
    return tracing.is_compute(o) and tracing.is_collective(o)


def _other_compute(o) -> bool:
    return tracing.is_compute(o) and not tracing.is_collective(o)


def read(r):
    t = r.trace
    worst, seen = 0.0, False
    for plane in t.devices:
        coll = (tracing.window_ops(t, plane, _sync_collective)
                + tracing.window_ops(t, plane, tracing.is_collective, tracing.ASYNC_LINE))
        seen |= bool(coll)
        worst = max(worst, tracing.uncovered(coll, tracing.window_ops(t, plane, _other_compute)))
    return 100.0 * worst / (t.window[1] - t.window[0]) if seen else None
