"""Model weights made from a seed, in the parameter layout the program trains.

The benchmark makes the weights itself, so that the reference can make the
same ones again after the window without taking anything from the program.
The layout (leaf names and shapes) is the program's interface; ``program.py``
checks it against the program's own ``init_params`` shapes before a run.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

# a sequence's tokens, the weights and each step's batch come from their own
# stream of the seed
WEIGHTS_STREAM = 0


def seed_key(seed: int, stream: int):
    """A threefry key from any non-negative whole seed (not cut to 32 bits)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32), impl="threefry2x32")


def padded_vocab(model: dict) -> int:
    """Embedding and head rows: the vocabulary padded to 128, as the program does."""
    return -(-model["vocab_size"] // 128) * 128


def layout(model: dict) -> dict:
    """Leaf -> (init rule, shape), nested as the program's parameter tree."""
    d, n = model["hidden_size"], model["num_hidden_layers"]
    dh = model["head_dim"]
    qd, kvd = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    ff, vp = model["intermediate_size"], padded_vocab(model)

    def norm(stack=()):
        out = {"w": ("ones", stack + (d,))}
        if model["norm"] == "layernorm":
            out["b"] = ("zeros", stack + (d,))
        return out

    def dense(*shape):
        return ("dense", (n,) + shape)

    mlp = {"wu": dense(d, ff), "wd": dense(ff, d)}
    if model["hidden_act"] == "silu":
        mlp["wg"] = dense(d, ff)
    layer = {"norm1": norm((n,)), "norm2": norm((n,)), "mlp": mlp,
             "attn": {"wq": dense(d, qd), "wk": dense(d, kvd), "wv": dense(d, kvd),
                      "wo": dense(qd, d)}}
    return {"embed": ("embed", (vp, d)), "cycles": {"pos0": layer},
            "final_norm": norm(), "head": ("dense", (d, vp))}


def _leaf(rule, shape, key, dtype):
    if rule == "ones":
        return jnp.ones(shape, dtype)
    if rule == "zeros":
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    scale = 0.02 if rule == "embed" else shape[-2] ** -0.5  # fan-in
    return (x * scale).astype(dtype)


def is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)


def make_fn(model: dict):
    """key -> parameter tree in ``model['param_dtype']``; jit it (one call)."""
    spec = layout(model)
    leaves, tree = jax.tree.flatten(spec, is_leaf=is_spec)
    dtype = jnp.dtype(model["param_dtype"])

    def make(key):
        return jax.tree.unflatten(tree, [
            _leaf(rule, shape, jax.random.fold_in(key, i), dtype)
            for i, (rule, shape) in enumerate(leaves)])

    return make


def make(model: dict, seed: int, out_shardings=None):
    """The seed's weights, made on the device in one jitted call."""
    return jax.jit(make_fn(model), out_shardings=out_shardings)(seed_key(seed, WEIGHTS_STREAM))
