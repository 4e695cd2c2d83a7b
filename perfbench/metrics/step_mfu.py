"""step_mfu: the whole step's model FLOPs per second over the chips' bf16 peak.

Model FLOPs of a step are the forward and a backward of twice the forward
(``flops.step_flops``: causal attention, head included, no recompute); the
time is the window's on the host clock, all steps in it.
"""
from perfbench import flops


def read(r):
    fl = flops.step_flops(r.model, r.traffic["batch"], r.traffic["seq"]) * r.steps
    return 100.0 * fl / (r.window_s * r.chips * r.peak["bf16_flops_per_s"])
