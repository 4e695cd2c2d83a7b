"""pallas_kernel_s_per_step: device seconds per step spent in Pallas (Mosaic)
kernels, averaged over the chips.

Mosaic kernels are the ``custom-call`` ops with the target
``tpu_custom_call``; on the FPDT path they are the flash-attention kernels
(fwd, dq, dkv), which the trace does not name apart (see PERF.md).  A trace
with none reads nothing.
"""
from perfbench import tracing


def is_pallas(op) -> bool:
    return op.opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in op.text


def read(r):
    ns = sum(e - s for p in r.trace.devices
             for s, e in tracing.window_ops(r.trace, p, is_pallas)) / r.chips
    return ns / 1e9 / r.steps if ns > 0 else None
