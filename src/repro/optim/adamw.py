"""AdamW with sharded (ZeRO-3-equivalent) optimizer state.

States inherit the parameter shardings (params themselves are sharded over
the data axes = ZeRO-3), so m/v never materialize unsharded.  State dtype is
configurable (bf16 for the 780B llama4 config so per-chip state fits v5e HBM).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    state_dtype: str = "float32"


class OptState(NamedTuple):
    step: jnp.ndarray
    m: Any
    v: Any


def lr_at(oc: OptConfig, step) -> jnp.ndarray:
    """Linear warmup + cosine decay to min_lr_frac."""
    step = jnp.asarray(step, jnp.float32)
    warm = step / jnp.maximum(1.0, oc.warmup_steps)
    prog = (step - oc.warmup_steps) / jnp.maximum(1.0, oc.total_steps - oc.warmup_steps)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = oc.min_lr_frac + (1 - oc.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return oc.lr * jnp.where(step < oc.warmup_steps, warm, cos)


def init(oc: OptConfig, params) -> OptState:
    dt = jnp.dtype(oc.state_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    return OptState(step=jnp.zeros((), jnp.int32),
                    m=jax.tree.map(zeros, params),
                    v=jax.tree.map(zeros, params))


def global_norm(grads) -> jnp.ndarray:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )


@jax.named_scope("adamw")
def apply(oc: OptConfig, params, grads, state: OptState):
    """Returns (new_params, new_state, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, oc.clip_norm / (gnorm + 1e-9))
    step = state.step + 1
    lr = lr_at(oc, step)
    c1 = 1.0 - oc.b1 ** step.astype(jnp.float32)
    c2 = 1.0 - oc.b2 ** step.astype(jnp.float32)
    dt = jnp.dtype(oc.state_dtype)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m1 = oc.b1 * m.astype(jnp.float32) + (1 - oc.b1) * g
        v1 = oc.b2 * v.astype(jnp.float32) + (1 - oc.b2) * g * g
        mh, vh = m1 / c1, v1 / c2
        step_w = mh / (jnp.sqrt(vh) + oc.eps) + oc.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step_w).astype(p.dtype), m1.astype(dt), v1.astype(dt)

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.m)
    flat_v = jax.tree.leaves(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree.unflatten(tdef, [o[2] for o in out])
    return new_p, OptState(step, new_m, new_v), {"grad_norm": gnorm, "lr": lr}
