"""Common model layers: norms, RoPE, attention projections, MLP.

Everything is a pure function over explicit param pytrees; parameter
initialization lives next to each layer.  Sharding is expressed by the
caller via ``repro.core.parallel.shard`` constraints — layer code is
mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig

Params = Dict[str, Any]


def _dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dtype) -> Params:
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((cfg.d_model,), dtype), "b": jnp.zeros((cfg.d_model,), dtype)}
    return {"w": jnp.ones((cfg.d_model,), dtype)}


def apply_norm(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        return (y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + 1e-6)
    return (y * p["w"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [b, s, h, d]; positions: [s] or [b, s] global token positions."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [d/2]
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., s, d/2]
    if ang.ndim == 2:  # [s, d/2] -> broadcast over batch
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]  # [b, s, 1, d/2]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(seq_len: int, d_model: int, offset: int = 0) -> jnp.ndarray:
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d_model)
    out = jnp.zeros((seq_len, d_model), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return out


# ---------------------------------------------------------------------------
# Attention projections (the mixer itself is injected — ulysses/fpdt/cp)
# ---------------------------------------------------------------------------


def init_attn(cfg: ModelConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": _dense_init(ks[0], (d, qd), dtype),
        "wk": _dense_init(ks[1], (d, kvd), dtype),
        "wv": _dense_init(ks[2], (d, kvd), dtype),
        "wo": _dense_init(ks[3], (qd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    return p


def qkv_proj(cfg: ModelConfig, p: Params, x: jnp.ndarray):
    """x [b,s,d] -> q [b,s,hq,dh], k,v [b,s,hkv,dh]."""
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def out_proj(cfg: ModelConfig, p: Params, o: jnp.ndarray) -> jnp.ndarray:
    b, s = o.shape[:2]
    return o.reshape(b, s, cfg.q_dim) @ p["wo"]


# ---------------------------------------------------------------------------
# MLP (dense; MoE lives in models/moe.py). Chunked per the paper §5.4.
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, dtype) -> Params:
    d, ff = cfg.d_model, cfg.d_ff
    if cfg.mlp_act == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "wg": _dense_init(k1, (d, ff), dtype),
            "wu": _dense_init(k2, (d, ff), dtype),
            "wd": _dense_init(k3, (ff, d), dtype),
        }
    k1, k2 = jax.random.split(key, 2)
    return {"wu": _dense_init(k1, (d, ff), dtype), "wd": _dense_init(k2, (ff, d), dtype)}


def mlp_block(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.mlp_act == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
    return jax.nn.gelu(x @ p["wu"]) @ p["wd"]


@jax.named_scope("mlp")
def mlp_chunked(cfg: ModelConfig, p: Params, x: jnp.ndarray, n_chunks: int) -> jnp.ndarray:
    """Paper §5.4: token-wise ops chunked along the sequence (no offload —
    O(N) compute can never hide transfer latency).  Implemented as a
    rematerialized lax.scan over sequence chunks so both forward peak memory
    and backward recompute are bounded by one chunk."""
    if n_chunks <= 1 or x.shape[1] % n_chunks != 0:
        return mlp_block(cfg, p, x)
    b, s, d = x.shape
    xs = x.reshape(b, n_chunks, s // n_chunks, d).transpose(1, 0, 2, 3)

    @jax.checkpoint
    def step(_, xc):
        return None, mlp_block(cfg, p, xc)

    _, ys = jax.lax.scan(step, None, xs)
    return ys.transpose(1, 0, 2, 3).reshape(b, s, d)
