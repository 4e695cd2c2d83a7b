"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
asserting output shapes + finiteness (assignment deliverable f)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.models import transformer as T


def make_batch(cfg, key, b, s):
    batch = {}
    if cfg.frontend == "audio_frames":
        batch["frame_embeds"] = jax.random.normal(key, (b, s, cfg.d_model), jnp.bfloat16)
        batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    elif cfg.frontend == "vision_patches":
        st = s - cfg.num_patches
        batch["patch_embeds"] = jax.random.normal(key, (b, cfg.num_patches, cfg.d_model), jnp.bfloat16)
        batch["tokens"] = jax.random.randint(key, (b, st), 0, cfg.vocab_size)
        batch["labels"] = jax.random.randint(key, (b, st), 0, cfg.vocab_size)
    else:
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
        batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_smoke_forward_and_train_step(name):
    cfg = reduced(get_config(name))
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    batch = make_batch(cfg, key, 2, 32)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: T.loss_fn(cfg, None, p, batch), has_aux=True
    )(params)
    assert np.isfinite(float(loss)), name
    assert 1.0 < float(loss) < 20.0, (name, float(loss))  # ~ln(vocab) at init
    gsum = jax.tree.reduce(
        lambda a, g: a + jnp.sum(jnp.abs(g.astype(jnp.float32))), grads, 0.0
    )
    assert np.isfinite(float(gsum)) and float(gsum) > 0, name
    # one SGD step moves the loss
    params2 = jax.tree.map(lambda p, g: p - 0.02 * g.astype(p.dtype), params, grads)
    loss2, _ = T.loss_fn(cfg, None, params2, batch)
    assert float(loss2) < float(loss), (name, float(loss), float(loss2))


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_full_config_matches_assignment(name):
    """The full (non-reduced) configs carry the assigned dims exactly."""
    cfg = get_config(name)
    expected = {
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "yi-34b": (60, 7168, 56, 8, 20480, 64000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
    }[name]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (name, got, expected)
    if name == "granite-moe-1b-a400m":
        assert (cfg.num_experts, cfg.experts_per_token) == (32, 8)
    if name == "llama4-maverick-400b-a17b":
        assert (cfg.num_experts, cfg.experts_per_token) == (128, 1)
    if name == "falcon-mamba-7b":
        assert cfg.ssm_state == 16
    if name == "recurrentgemma-9b":
        assert cfg.window == 2048 and cfg.block_pattern == ("rglru", "rglru", "local_attn")


def _scope_paths(hlo: str):
    """Each op_name as its list of components, a component ``transpose(jvp(x))``
    read as the names ``transpose``, ``jvp``, ``x``."""
    import re

    return [[re.findall(r"[^()]+", c) for c in n.split("/")]
            for n in set(re.findall(r'op_name="([^"]*)"', hlo))]


def test_train_step_keeps_named_scopes_in_hlo():
    """The train step's compiled HLO keeps the program's scopes in op_name
    metadata, backward and recompute included: ``loss_head`` and ``mlp``
    under ``transpose(...)``, the FPDT projections, and ``adamw``."""
    from repro.optim import adamw
    from repro.runtime.train_loop import make_train_step

    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), num_layers=1,
                              fpdt_chunks=2, mlp_chunks=2)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    oc = adamw.OptConfig()
    opt = jax.eval_shape(lambda p: adamw.init(oc, p), params)
    batch = {k: jax.ShapeDtypeStruct((1, 64), jnp.int32) for k in ("tokens", "labels")}
    hlo = jax.jit(make_train_step(cfg, None, oc)).lower(params, opt, batch).compile().as_text()
    paths = _scope_paths(hlo)

    def under(scope, backward):
        return [p for p in paths if any(scope in c for c in p)
                and any("transpose" in c for c in p) == backward]

    for scope in ("loss_head", "mlp", "fpdt.project"):
        assert under(scope, backward=False), scope
    for scope in ("loss_head", "mlp", "fpdt.project", "fpdt.unproject"):
        assert under(scope, backward=True), scope
    assert under("adamw", backward=False)
