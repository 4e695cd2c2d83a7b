"""device_idle_pct: share of the traced window in which no compute op runs
on a device (``tracing.is_compute``: waits on async ops are idle), the worst
device's."""
from perfbench import tracing


def read(r):
    t = r.trace
    w = t.window[1] - t.window[0]
    return 100.0 * max(1.0 - tracing.length(tracing.window_ops(t, p)) / w for p in t.devices)
