"""A run of a cell defined only by data files, with the timed path sound and
broken, on the CPU at a size a test run holds (no chip needed)."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from perfbench import check, faults, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "gpt-6.7b.fpdt-64k"
SEED = 2 ** 31 + 11
# The small cell's own limits: sound small runs read about 1e-5, 1e-3 and
# 4e-3 (fewer elements round coarser than at the cells' sizes); the control
# reads 2e-3 to 4e-3 on the loss, and a state left unchanged 1.
SMALL_LIMITS = {"steps": 3, "loss_gap": 2e-4, "grad_gap": 1e-2, "update_gap": 1.5e-2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose BENCHMARK.json holds one new cell: new data files only."""
    r = tmp_path_factory.mktemp("checkout")
    data = r / "perfbench"
    for d in ("configs", "traffic", "limits"):
        (data / d).mkdir(parents=True)
    model = json.load(open(os.path.join(ROOT, "perfbench", "configs", "gpt-6.7b.json")))
    model.update(name="small-gpt", hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                 head_dim=16, intermediate_size=256, vocab_size=512)
    (data / "configs" / "small-gpt.json").write_text(json.dumps(model))
    (data / "traffic" / "small.json").write_text(json.dumps(
        {"seq": 128, "batch": 2, "chunks": 4, "offload": True, "remat": "full", "mesh": None}))
    (data / "limits" / "small-gpt.small.json").write_text(json.dumps(SMALL_LIMITS))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"] = [{"name": "small-gpt", "source": "test", "file": "perfbench/configs/small-gpt.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "small-gpt.small", "config": "small-gpt", "traffic": "small",
                           "chips": 1, "why": "test"}]
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(r)


def _run(root):
    return harness.run(root, "small-gpt.small", SEED, 0.5, False, time.perf_counter(),
                       require_tpu=False)


def test_data_only_cell_runs_and_is_correct(root):
    r = _run(root)
    assert r["correct"], r["checked"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert list(r)[-1] == "checked" and set(r["checked"]) == set(check.NUMBERS)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_step_is_not_correct(root, fault):
    undo = faults.plant(fault)
    try:
        r = _run(root)
    finally:
        undo()
    assert not r["correct"], r["checked"]


def test_control_is_not_correct(root):
    spec = harness.load_cell(root, "small-gpt.small")
    steps, one = spec["limits"]["steps"], jax.devices()[:1]
    ref, _ = harness.reference_readings(spec["model"], spec["traffic"], SEED, steps, one)
    low, _ = harness.reference_readings(spec["model"], spec["traffic"], SEED, steps, one,
                                        mode="fp8")
    ok, shown = check.judge(check.numbers(low, ref), spec["limits"])
    assert not ok, shown


def test_run_without_the_program_or_a_chip_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload", CELL, "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
