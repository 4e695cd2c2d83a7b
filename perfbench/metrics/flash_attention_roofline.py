"""flash_attention_roofline: the least time the step's causal attention needs
on the cell's chips over the time its kernels ran.

The least time is the larger of the algorithm's FLOPs over the chips' bf16
peak and its bytes over their HBM bandwidth (``flops.attention_work``: the
forward and backward of causal attention, not the calls' recompute).  The
kernels' time is the summed device time of the attention kernel events in
the window, over the chips.  Events are matched by name: the Mosaic kernel
bodies (``_fwd_kernel``, ``_dq_kernel``, ``_dkv_kernel``) or names beginning
``flash_attention``.  A trace with no such event is an error.
"""
import re

from perfbench import flops, tracing

ATTENTION = re.compile(r"(?<![A-Za-z0-9])(flash_attention\w*|_fwd_kernel|_dq_kernel|_dkv_kernel)(?!\w)")


def is_attention(op) -> bool:
    return bool(ATTENTION.search(op.text))


def read(r):
    ns = sum(e - s for p in r.trace.devices
             for s, e in tracing.window_ops(r.trace, p, is_attention)) / r.chips
    if ns <= 0:
        raise RuntimeError("no attention kernel event in the trace")
    w = flops.attention_work(r.model, r.traffic["batch"], r.traffic["seq"])
    t_flops = w["flops"] * r.steps / r.chips / r.peak["bf16_flops_per_s"]
    t_bytes = w["bytes"] * r.steps / r.chips / r.peak["hbm_bytes_per_s"]
    print(f"[perfbench] flash_attention_roofline: {'compute' if t_flops >= t_bytes else 'memory'}"
          f"-bound (least {max(t_flops, t_bytes):.4f} s; kernels {ns / 1e9:.4f} s per chip)")
    return 100.0 * max(t_flops, t_bytes) / (ns / 1e9)
