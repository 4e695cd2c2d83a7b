"""How a configuration file reaches the program's ``ModelConfig``: every key
carried or refused by name, and the eps the program's norm adds read back."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import program

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = sorted(os.path.basename(p)[:-5]
                 for p in glob.glob(os.path.join(ROOT, "perfbench", "configs", "*.json")))
TRAFFIC = {"chunks": 4, "offload": True, "remat": "full"}


def _config(name: str) -> dict:
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


def _norm_adding(eps: float):
    """An ``apply_norm`` that adds ``eps``, whatever the config says."""
    def apply_norm(cfg, p, x):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True) if cfg.norm == "layernorm" else 0.0
        y = (xf - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(xf - mu), -1, keepdims=True) + eps)
        y = y * p["w"]
        return y + p["b"] if cfg.norm == "layernorm" else y
    return apply_norm


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_reaches_the_program(name, monkeypatch):
    """With a norm that adds the file's eps, every file's keys reach the step."""
    from repro.models import layers

    model = _config(name)
    monkeypatch.setattr(layers, "apply_norm", _norm_adding(model["norm_eps"]))
    cfg = program.model_config(model, TRAFFIC)
    assert program.norm_eps_of(cfg, model["norm_eps"]) == pytest.approx(model["norm_eps"],
                                                                          rel=1e-4)
    assert cfg.tie_embeddings == model.get("tie_word_embeddings", False)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size, cfg.norm, cfg.rope_theta, cfg.param_dtype) == (
        model["num_hidden_layers"], model["hidden_size"], model["num_attention_heads"],
        model["num_key_value_heads"], model["head_dim"], model["intermediate_size"],
        model["vocab_size"], model["norm"], model["rope_theta"], model["param_dtype"])


def test_eps_the_program_does_not_add_is_refused(monkeypatch):
    from repro.models import layers

    monkeypatch.setattr(layers, "apply_norm", _norm_adding(1e-6))
    model = dict(_config("gpt-6.7b"), norm_eps=1e-6)
    assert program.model_config(model, TRAFFIC).norm == "layernorm"
    with pytest.raises(program.Refused, match="norm_eps") as e:
        program.model_config(dict(model, norm_eps=1e-5), TRAFFIC)
    assert e.value.key == "norm_eps"


def test_gpt_builds_the_same_step_as_before():
    """The program as it is adds LayerNorm's 1e-5, and the gpt cells' config
    is the one the benchmark has always built (no tying)."""
    from repro.configs import ModelConfig

    cfg = program.model_config(_config("gpt-6.7b"), TRAFFIC)
    assert cfg == ModelConfig(
        name="gpt-6.7b", family="dense", num_layers=2, d_model=4096, num_heads=32,
        num_kv_heads=32, head_dim=128, d_ff=16384, vocab_size=50304, mlp_act="gelu",
        norm="layernorm", rope_theta=10000.0, param_dtype="bfloat16",
        opt_state_dtype="bfloat16", fpdt_chunks=4, mlp_chunks=8, fpdt_offload=True,
        remat="full")


def test_unknown_key_is_refused_by_name():
    with pytest.raises(program.Refused, match="sliding_window") as e:
        program.model_config(dict(_config("gpt-6.7b"), sliding_window=4096), TRAFFIC)
    assert e.value.key == "sliding_window"


@pytest.mark.parametrize("key,value", [
    ("position_embedding", "learned"), ("norm", "groupnorm"), ("hidden_act", "relu"),
    ("tie_word_embeddings", True)])
def test_value_the_benchmark_cannot_run_is_refused(key, value):
    with pytest.raises(program.Refused, match=key) as e:
        program.model_config(dict(_config("gpt-6.7b"), **{key: value}), TRAFFIC)
    assert e.value.key == key
