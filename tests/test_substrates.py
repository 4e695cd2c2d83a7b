"""Data pipeline, optimizer, compression, checkpointing, runtime FT."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import CheckpointableIterator, DataConfig, make_batch_fn
from repro.checkpoint.manager import CheckpointManager
from repro.configs import SHAPES, ShapeConfig, get_config, reduced
from repro.optim import adamw
from repro.optim import compression as comp


# ---------------------------------------------------------------- data
def test_data_determinism_and_resume():
    cfg = reduced(get_config("llama3.2-1b"))
    shape = ShapeConfig("t", 32, 4, "train")
    bf = make_batch_fn(cfg, shape)
    a = bf(7)
    b = bf(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    it = CheckpointableIterator(bf)
    for _ in range(3):
        next(it)
    state = it.state()
    want = next(it)["tokens"]
    it2 = CheckpointableIterator(bf)
    it2.restore(state)
    np.testing.assert_array_equal(next(it2)["tokens"], want)


def test_data_labels_shifted():
    cfg = reduced(get_config("llama3.2-1b"))
    bf = make_batch_fn(cfg, ShapeConfig("t", 16, 2, "train"))
    b = bf(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ---------------------------------------------------------------- optimizer
def test_adamw_matches_reference():
    oc = adamw.OptConfig(lr=1e-2, warmup_steps=0, total_steps=100,
                         weight_decay=0.0, clip_norm=1e9, min_lr_frac=1.0)
    params = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    grads = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    st = adamw.init(oc, params)
    p1, st1, m = adamw.apply(oc, params, grads, st)
    # closed-form first Adam step: p - lr * sign-ish
    g = np.asarray([0.1, 0.2, -0.3])
    mh = g  # m1/c1 with b1 bias correction
    vh = g * g
    want = np.asarray([1.0, -2.0, 3.0]) - 1e-2 * mh / (np.sqrt(vh) + oc.eps)
    np.testing.assert_allclose(np.asarray(p1["w"]), want, rtol=1e-5)


def test_lr_schedule():
    oc = adamw.OptConfig(lr=1.0, warmup_steps=10, total_steps=110, min_lr_frac=0.1)
    assert float(adamw.lr_at(oc, 5)) == pytest.approx(0.5)
    assert float(adamw.lr_at(oc, 10)) == pytest.approx(1.0)
    assert float(adamw.lr_at(oc, 110)) == pytest.approx(0.1, rel=1e-3)


def test_grad_clipping():
    oc = adamw.OptConfig(clip_norm=1.0, warmup_steps=0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    grads = {"w": jnp.full(4, 100.0)}
    st = adamw.init(oc, params)
    _, _, m = adamw.apply(oc, params, grads, st)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


# ---------------------------------------------------------------- compression
def test_compression_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(5000), jnp.float32)
    res = jnp.zeros(5000, jnp.float32)
    # accumulated (g_hat) over steps tracks accumulated g (error feedback)
    tot_hat = np.zeros(5000)
    for _ in range(20):
        g_hat, res = comp.quantize_with_feedback(g, res)
        tot_hat += np.asarray(g_hat)
    drift = np.abs(tot_hat - 20 * np.asarray(g)).max()
    scale = np.abs(np.asarray(g)).max() / 127
    assert drift <= 2 * scale + 1e-5  # residual bounded -> no accumulation


def test_compression_roundtrip_small_error():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((333,)), jnp.float32)
    c = comp.compress(x)
    y = comp.decompress(c, x.shape, jnp.float32)
    assert float(jnp.max(jnp.abs(x - y))) <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6


# ---------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), shards_per_leaf=3, keep=2)
    tree = {"a": jnp.arange(10, dtype=jnp.float32).reshape(5, 2),
            "b": {"c": jnp.ones((7,), jnp.bfloat16)}, "s": jnp.int32(3)}
    for step in (1, 2, 3):
        mgr.save(step, tree, extra={"data_step": step * 10}, blocking=True)
    assert mgr.all_steps() == [2, 3]  # gc keeps last 2
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    got, extra = mgr.restore(3, like)
    assert extra["data_step"] == 30
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.ones((64, 64))}
    mgr.save(5, tree)  # async
    mgr.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_atomicity(tmp_path):
    """A .tmp dir (simulated crash) must be invisible to restore."""
    mgr = CheckpointManager(str(tmp_path))
    os.makedirs(tmp_path / "step_9.tmp")
    assert mgr.latest_step() is None
    mgr.save(1, {"w": jnp.zeros(3)}, blocking=True)
    assert mgr.latest_step() == 1


# ---------------------------------------------------------------- runtime FT
def test_straggler_monitor():
    from repro.runtime.train_loop import HeartbeatMonitor, StragglerAlert

    mon = HeartbeatMonitor(zscore=3.0, patience=2)
    for _ in range(200):
        mon.record(0.1 + np.random.default_rng(0).uniform(0, 0.001))
    assert len(mon.times) == HeartbeatMonitor.HISTORY + 1  # bounded history
    with pytest.raises(StragglerAlert):
        mon.record(5.0)
        mon.record(5.0)


def test_train_loop_end_to_end(tmp_path):
    """Tiny model, few steps; checkpoint + resume continues identically."""
    import dataclasses

    from repro.models import transformer as T
    from repro.runtime.train_loop import TrainConfig, TrainLoop, make_train_step

    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), num_layers=2)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    oc = adamw.OptConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    opt = adamw.init(oc, params)
    tc = TrainConfig(steps=6, ckpt_every=3, log_every=100)
    step_fn = jax.jit(make_train_step(cfg, None, oc, tc))
    bf = make_batch_fn(cfg, ShapeConfig("t", 32, 2, "train"))

    def put(b):
        return {k: jnp.asarray(v) for k, v in b.items()}

    mgr = CheckpointManager(str(tmp_path))
    loop = TrainLoop(cfg, None, oc, tc, step_fn, CheckpointableIterator(bf), mgr)
    params_f, opt_f, step = loop.run(params, opt, put_batch=put)
    assert step == 6
    assert mgr.latest_step() == 6
    losses = [h["loss"] for h in loop.history]
    assert losses[-1] < losses[0]  # training moves the loss

    # resume from step 3 and land on the same trajectory
    (restored, extra) = mgr.restore(3, {"params": params, "opt": opt})
    loop2 = TrainLoop(cfg, None, oc, tc, step_fn, CheckpointableIterator(bf), None)
    params_r, opt_r, step_r = loop2.run(restored["params"], restored["opt"],
                                        start_step=3, put_batch=put)
    assert step_r == 6
    for a, b in zip(jax.tree.leaves(params_r), jax.tree.leaves(params_f)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)
