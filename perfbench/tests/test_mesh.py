"""A four-chip cell defined only by data files, and the reference over four
devices, on four virtual CPU devices (``mesh_cases.py``, run once in a
process of its own, as the repo's mesh tests run theirs)."""
import json
import os
import subprocess
import sys

import pytest

from perfbench import check

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# The reference over four devices sums in another order than over one (its
# layers' partial outputs, the loss over vocabulary parts, the gradients'
# reduction) and nothing else changes, so loss and gradient agree to float32
# round-off: on the small cell 7e-8 and 6.5e-7.  The update can differ more:
# where round-off moves an element's float32 update across a rounding
# boundary of its bfloat16 parameter, that element moves by one bfloat16
# step more or less; the small cell reads 3.1e-5.  Each bound leaves room
# and stays under the program's own gaps (1e-4, 1e-3, 1.4e-3 here).
ROUND_OFF = {"loss_gap": 1e-6, "grad_gap": 1e-5, "update_gap": 1e-4}


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, os.path.join(HERE, "mesh_cases.py"),
                        str(tmp_path_factory.mktemp("mesh_checkout"))],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


def test_mesh_cell_runs_and_is_correct(cases):
    r = cases["sound"]
    assert r["correct"], r["checked"]
    assert r["count"] == 4 and r["attempted"] >= 1 and r["failed"] == 0
    assert r["metrics"] == ["peak_hbm_gib", "setup_s", "train_tokens_per_s"]


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_broken_mesh_step_is_not_correct(cases, fault):
    assert not cases[fault]["correct"], cases[fault]["checked"]


@pytest.mark.parametrize("mode", ["fp8", "no_exchange"])
def test_control_and_exchange_left_out_are_not_correct(cases, mode):
    assert not cases[mode]["correct"], cases[mode]["checked"]


def test_reference_over_four_devices_equals_one(cases):
    got = cases["four_vs_one"]
    assert all(got[k] <= ROUND_OFF[k] for k in check.NUMBERS), got


@pytest.mark.parametrize("shardings", ["program", "reference"])
def test_weights_under_shardings_are_the_same_bits(cases, shardings):
    assert cases["same_bits_" + shardings]
    assert cases["program_splits"]
