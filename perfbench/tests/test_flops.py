"""The benchmark's copied FLOP arithmetic against the program's analytic model."""
import dataclasses
import importlib.util
import json
import os

import pytest

from perfbench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "perfbench", "configs")


def _program_model():
    spec = importlib.util.spec_from_file_location(
        "flops_model", os.path.join(ROOT, "benchmarks", "flops_model.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# (configuration file or program arch, batch, seq): the cells' sizes, and the
# four-chip cell the Open questions keep for later
CELLS = [("gpt-6.7b", 1, 65536), ("gpt-6.7b", 4, 8192), ("mistral-nemo-12b", 1, 65536)]
MISTRAL = {"name": "mistral-nemo-12b", "num_hidden_layers": 2, "hidden_size": 5120,
           "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
           "intermediate_size": 14336, "vocab_size": 131072, "hidden_act": "silu"}


@pytest.mark.parametrize("arch,batch,seq", CELLS)
def test_step_flops_are_three_program_forwards(arch, batch, seq):
    from repro.configs import get_config

    path = os.path.join(CONFIGS, arch + ".json")
    model = json.load(open(path)) if os.path.exists(path) else MISTRAL
    cfg = dataclasses.replace(get_config(arch), num_layers=model["num_hidden_layers"])
    theirs = _program_model().fwd_flops(cfg, batch, seq)
    ours = flops.fwd_flops(model, batch, seq)
    for k in ("proj", "attention", "mlp", "head"):
        assert ours[k] == pytest.approx(theirs[k], rel=1e-12)
    assert flops.step_flops(model, batch, seq) == pytest.approx(3 * sum(theirs.values()), rel=1e-12)


def test_attention_work_is_three_causal_forwards():
    model = json.load(open(os.path.join(CONFIGS, "gpt-6.7b.json")))
    w = flops.attention_work(model, 1, 65536)
    # 2 layers x 4 x S(S+1)/2 x q_dim, forward and a backward of twice it
    assert w["flops"] == 3 * 2 * 4 * (65536 * 65537 / 2) * 4096
    assert w["bytes"] == 2 * 65536 * (6 * 4096 + 6 * 4096) * 2
