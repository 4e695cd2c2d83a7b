"""Compile-scaling regression: the scan-compiled FPDT pipeline's program
size must stay ~flat in the chunk count u, so nobody silently introduces
an unrolled (O(u^2)) chunk schedule on the path to the paper's 2M-token
configs.  Measured: traced jaxpr equations and lowered StableHLO op count
at u=32 vs u=4 (value_and_grad, so the Fig. 7 backward is included).
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

import compile_scaling as CS


@pytest.mark.slow
def test_scan_path_near_constant_in_u():
    r4 = CS.measure(4)
    r32 = CS.measure(32)
    assert r32["jaxpr_eqns"] <= 2 * r4["jaxpr_eqns"], (r4, r32)
    assert r32["hlo_ops"] <= 2 * r4["hlo_ops"], (r4, r32)
