"""host_copy_exposed_pct: share of the traced window in which a transfer
between host and device memory is in flight and no compute op runs on that
device, the worst device's.

In flight: an ``Async XLA Ops`` event that touches pinned host memory
(``tracing.is_host_transfer``); compute: ``tracing.is_compute``.  A trace
with no host transfer reads nothing.
"""
from perfbench import tracing


def read(r):
    t = r.trace
    worst, seen = 0.0, False
    for plane in t.devices:
        flight = tracing.window_ops(t, plane, tracing.is_host_transfer, tracing.ASYNC_LINE)
        seen |= bool(flight)
        worst = max(worst, tracing.uncovered(flight, tracing.window_ops(t, plane)))
    return 100.0 * worst / (t.window[1] - t.window[0]) if seen else None
