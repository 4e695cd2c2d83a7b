"""Plain reference of the trained model and its optimizer.

Straight ``jax.numpy`` in float32 at the highest matmul precision, written
from the configuration file alone: it imports nothing of the program and
takes nothing the program made (it makes the seed's weights again with
``weights.py``).  What it computes, as the configuration states it:

  * token embedding; per layer a pre-norm block: norm, q/k/v projections,
    rotary positions (rotate-half, ``rope_theta``) on q and k, causal softmax
    attention with a 1/sqrt(head_dim) scale (kv head of q head h: h // group),
    output projection, residual; norm, MLP (``gelu_new``: tanh GELU;
    ``silu``: SwiGLU), residual;
  * final norm, head over the padded vocabulary, mean next-token
    cross-entropy;
  * AdamW with global-norm clipping and the warmup-cosine schedule; weights
    and moments are kept in the dtypes the configuration states
    (``param_dtype``, ``optimizer.state_dtype``), the update in float32.

It runs in blocks so that the timed sizes fit one chip: attention by blocks
of query rows, each over the keys up to the end of its group of rows, and
the head and loss by blocks of tokens; layers and blocks are recomputed in
the backward pass.

Over several devices (a cell's chips) it runs under ``shard_map`` on a
one-axis mesh, and each device does about its share of the work: every leaf
is split on one axis (``leaf_specs``), so the weights, their float32 copy,
the gradients and the Adam moments are too; the residual stream and the
norms are split by rows of the sequence; attention by heads (each device's
query heads with the KV heads they use), the MLP by columns of d_ff, and the
embedding and the head with the loss by rows of the vocabulary.  A layer
gathers the normed rows of the whole sequence before attention and before the
MLP, and sums and splits its partial outputs back into rows after them.  On
one device it builds the same programs as ever.

``mode="fp8"`` is the control: every matmul takes its inputs in float8
(e4m3 forward, e5m2 for the cotangents of the backward, one scale per
tensor, or per device's part of it over several devices) and accumulates in
float32; nothing else changes.  ``mode="no_exchange"`` (several devices
only) is a fault: each layer's partial outputs are not summed over the
devices, so each device's rows take only its own heads' and MLP columns'
part.
"""
from __future__ import annotations

import functools
import json

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import weights

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# a block's score or logit buffer stays under this many bytes
BLOCK_BYTES = 1 << 29
MASKED = -1e30
AXIS = "chips"  # the one mesh axis over a cell's devices


# --------------------------------------------------------------------------- matmuls


def _mm32(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=F32)


def _fake_quant(x, dtype):
    """x rounded to a float8 type under one per-tensor scale (max |x| -> max)."""
    top = float(jnp.finfo(dtype).max)
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _q_fwd(x):
    """Forward operand in e4m3; the cotangent passes through unchanged."""
    return _fake_quant(x, jnp.float8_e4m3fn)


_q_fwd.defvjp(lambda x: (_fake_quant(x, jnp.float8_e4m3fn), None), lambda _, g: (g,))


@jax.custom_vjp
def _q_bwd(y):
    """Identity forward; the cotangent is rounded to e5m2 before it is used."""
    return y


_q_bwd.defvjp(lambda y: (y, None), lambda _, g: (_fake_quant(g, jnp.float8_e5m2),))


def _mm8(spec, a, b):
    return _q_bwd(jnp.einsum(spec, _q_fwd(a), _q_fwd(b), precision=HIGHEST,
                             preferred_element_type=F32))


# mode -> (matmul, whether a layer's partial outputs are summed over devices)
MODES = {"fp32": (_mm32, True), "fp8": (_mm8, True), "no_exchange": (_mm32, False)}


# --------------------------------------------------------------------------- model


def _norm(model, p, x):
    eps = model["norm_eps"]
    if model["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["w"]


def _rope(x, theta):
    """x [b, s, h, dh]; rotate-half rotary positions 0..s-1."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv  # [s, dh/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _pow2_rows(rows, per_row_bytes):
    """Largest power of two dividing ``rows`` whose block fits BLOCK_BYTES."""
    r = 1
    while rows % (2 * r) == 0 and 2 * r * per_row_bytes <= BLOCK_BYTES:
        r *= 2
    return r


def attention(q, k, v, mm, groups=8):
    """Causal softmax attention. q [b, s, h, dh]; k, v [b, s, hkv, dh]."""
    b, s, h, dh = q.shape
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
    while s % groups:
        groups //= 2
    rows = s // groups
    bq = _pow2_rows(rows, b * h * s * 4)
    outs = []
    for gi in range(groups):
        klen = (gi + 1) * rows  # keys past the group's last row are all masked
        kk, vv = k[:, :klen], v[:, :klen]
        qg = q[:, gi * rows: klen].reshape(b, rows // bq, bq, h, dh).swapaxes(0, 1)
        starts = gi * rows + bq * jnp.arange(rows // bq)

        @jax.checkpoint
        def block(qb, start, kk=kk, vv=vv, klen=klen):
            sc = mm("bqhd,bkhd->bhqk", qb, kk) * dh ** -0.5
            live = jnp.arange(klen)[None, :] <= (start + jnp.arange(bq))[:, None]
            p = jax.nn.softmax(jnp.where(live, sc, MASKED), axis=-1)
            return mm("bhqk,bkhd->bqhd", p, vv)

        o = jax.lax.map(lambda a: block(*a), (qg, starts))
        outs.append(o.swapaxes(0, 1).reshape(b, rows, h, dh))
    return jnp.concatenate(outs, axis=1)


def _kv_heads_per_group(model) -> int:
    """KV heads per head group: whole GQA groups, about four query heads."""
    hkv = model["num_key_value_heads"]
    n = max(1, 4 * hkv // model["num_attention_heads"])
    while hkv % n:
        n -= 1
    return n


def _attention(model, mm, a, n):
    """attention(n) @ wo over the heads of ``a``, one group of heads at a time."""
    b, s, d = n.shape
    hq, hkv, dh = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    kvg = _kv_heads_per_group(model)
    groups, qg = hkv // kvg, kvg * hq // hkv
    ws = (a["wq"].reshape(d, groups, qg * dh).swapaxes(0, 1),
          a["wk"].reshape(d, groups, kvg * dh).swapaxes(0, 1),
          a["wv"].reshape(d, groups, kvg * dh).swapaxes(0, 1),
          a["wo"].reshape(groups, qg * dh, d))

    @jax.checkpoint
    def heads(n, wq, wk, wv, wo):
        q = _rope(mm("bsd,de->bse", n, wq).reshape(b, s, qg, dh), model["rope_theta"])
        k = _rope(mm("bsd,de->bse", n, wk).reshape(b, s, kvg, dh), model["rope_theta"])
        v = mm("bsd,de->bse", n, wv).reshape(b, s, kvg, dh)
        return mm("bse,ed->bsd", attention(q, k, v, mm).reshape(b, s, qg * dh), wo)

    y, _ = jax.lax.scan(lambda acc, w: (acc + heads(n, *w), None), jnp.zeros_like(n), ws)
    return y


def _attention_part(model, mm, p, x):
    """x + attention(norm1(x)) @ wo."""
    return x + _attention(model, mm, p["attn"], _norm(model, p["norm1"], x))


def _mlp(model, mm, m, n):
    """mlp(n), by blocks of tokens."""
    b, s, d = n.shape
    c = _pow2_rows(s, b * 3 * m["wu"].shape[1] * 4)
    ns = n.reshape(b, s // c, c, d).swapaxes(0, 1)

    @jax.checkpoint
    def block(nc):
        if model["hidden_act"] == "silu":
            y = jax.nn.silu(mm("bcd,df->bcf", nc, m["wg"])) * mm("bcd,df->bcf", nc, m["wu"])
        else:
            y = _gelu_tanh(mm("bcd,df->bcf", nc, m["wu"]))
        return mm("bcf,fd->bcd", y, m["wd"])

    return jax.lax.map(block, ns).swapaxes(0, 1).reshape(b, s, d)


def _mlp_part(model, mm, p, x):
    """x + mlp(norm2(x))."""
    return x + _mlp(model, mm, p["mlp"], _norm(model, p["norm2"], x))


def _layer(model, mm, p, x):
    return _mlp_part(model, mm, p, _attention_part(model, mm, p, x))


def loss(model, mm, p, tokens, labels):
    """Mean next-token cross-entropy of float32 parameters ``p``."""
    x = jnp.take(p["embed"], tokens, axis=0)
    layer = jax.checkpoint(functools.partial(_layer, model, mm))
    for i in range(model["num_hidden_layers"]):
        x = layer(jax.tree.map(lambda w: w[i], p["cycles"]["pos0"]), x)
    x = _norm(model, p["final_norm"], x)
    b, s, d = x.shape
    c = _pow2_rows(s, b * p["head"].shape[1] * 4)
    xs = x.reshape(b, s // c, c, d).swapaxes(0, 1)
    ys = labels.reshape(b, s // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def block(xc, yc):
        logits = mm("bcd,dv->bcv", xc, p["head"])
        tgt = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - tgt)

    return jnp.sum(jax.lax.map(lambda a: block(*a), (xs, ys))) / (b * s)


# --------------------------------------------------------------------------- several devices

# leaves split on their second-to-last axis (rows); every other leaf on its last
ROW_LEAVES = ("embed", "wo", "wd")


def leaf_specs(model: dict, n: int):
    """Each leaf's ``PartitionSpec`` over ``n`` devices on the axis ``AXIS``:
    the vocabulary rows of ``embed`` and ``head``, the query-head columns of
    ``wq`` and rows of ``wo``, the d_ff columns of ``wg``, ``wu`` and rows of
    ``wd``, the columns of ``wk``, ``wv`` and of the norms' ``d``."""
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    if hq % n or (hkv % n and n % hkv):
        raise ValueError(f"{hq} query and {hkv} KV heads cannot be split over {n} devices")

    def spec(path, leaf):
        _, shape = leaf
        name = path[-1].key
        ax = len(shape) - (2 if name in ROW_LEAVES else 1)
        if shape[ax] % n:
            raise ValueError(f"{jax.tree_util.keystr(path)} {shape} cannot be split over "
                             f"{n} devices")
        return P(*[AXIS if i == ax else None for i in range(len(shape))])

    return jax.tree_util.tree_map_with_path(spec, weights.layout(model), is_leaf=weights.is_spec)


def shardings(model: dict, devices):
    """The leaves' ``NamedSharding``s over the devices; ``None`` for one device."""
    if len(devices) <= 1:
        return None
    mesh = jax.sharding.Mesh(np.asarray(devices), (AXIS,))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), leaf_specs(model, len(devices)),
                        is_leaf=lambda x: isinstance(x, P))


def _gather(x, axis):
    return jax.lax.all_gather(x, AXIS, axis=axis, tiled=True)


def _norm_rows(model, w, x):
    """The norm of rows ``x`` with its weights gathered whole."""
    return _norm(model, jax.tree.map(lambda t: _gather(t, 0), w), x)


def _layer_rows(model, mm, exchange, p, x):
    """One layer on this device's rows x [b, s/n, d] of the sequence."""
    n, i = jax.lax.axis_size(AXIS), jax.lax.axis_index(AXIS)
    hq, hkv, dh = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    kv_here = max(1, hkv // n)
    local = dict(model, num_attention_heads=hq // n, num_key_value_heads=kv_here)
    rows = x.shape[1]

    def back_to_rows(y):
        """This device's rows of the sum over devices of the partial y [b, s, d]."""
        if exchange:
            return jax.lax.psum_scatter(y, AXIS, scatter_dimension=1, tiled=True)
        return jax.lax.dynamic_slice_in_dim(y, i * rows, rows, axis=1)

    def kv(w):
        """The columns of the KV heads that this device's query heads use."""
        first = i * (hq // n) // (hq // hkv)
        return jax.lax.dynamic_slice_in_dim(_gather(w, 1), first * dh, kv_here * dh, axis=1)

    a = dict(p["attn"], wk=kv(p["attn"]["wk"]), wv=kv(p["attn"]["wv"]))
    x = x + back_to_rows(_attention(local, mm, a, _gather(_norm_rows(model, p["norm1"], x), 1)))
    return x + back_to_rows(_mlp(model, mm, p["mlp"], _gather(_norm_rows(model, p["norm2"], x), 1)))


def _loss_rows(model, mm, exchange, p, tokens, labels):
    """The loss on one device: ``p`` holds its parts of the float32
    parameters, ``tokens`` and ``labels`` are whole."""
    i = jax.lax.axis_index(AXIS)

    def vocab_part(ids, table_rows):
        """ids - this device's first vocabulary row, and where they fall in its part."""
        t = ids - i * table_rows
        return jnp.clip(t, 0, table_rows - 1), (t >= 0) & (t < table_rows)

    t, here = vocab_part(tokens, p["embed"].shape[0])
    x = jnp.where(here[..., None], jnp.take(p["embed"], t, axis=0), 0.0)
    x = jax.lax.psum_scatter(x, AXIS, scatter_dimension=1, tiled=True)
    layer = jax.checkpoint(functools.partial(_layer_rows, model, mm, exchange))
    for li in range(model["num_hidden_layers"]):
        x = layer(jax.tree.map(lambda w: w[li], p["cycles"]["pos0"]), x)
    x = _gather(_norm_rows(model, p["final_norm"], x), 1)
    b, s, d = x.shape
    head = p["head"]
    c = _pow2_rows(s, b * head.shape[1] * 4)
    xs = x.reshape(b, s // c, c, d).swapaxes(0, 1)
    ys = labels.reshape(b, s // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def block(xc, yc):
        logits = mm("bcd,dv->bcv", xc, head)
        top = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(logits, -1)), AXIS)
        lse = jnp.log(jax.lax.psum(jnp.sum(jnp.exp(logits - top[..., None]), -1), AXIS)) + top
        t, here = vocab_part(yc, head.shape[1])
        tgt = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - jax.lax.psum(jnp.where(here, tgt, 0.0), AXIS))

    return jnp.sum(jax.lax.map(lambda a: block(*a), (xs, ys))) / (b * s)


# --------------------------------------------------------------------------- optimizer


def lr_at(opt: dict, step):
    """Linear warmup, then cosine decay to ``min_lr_frac`` of the peak."""
    step = jnp.asarray(step, F32)
    warm = step / max(1.0, opt["warmup_steps"])
    prog = jnp.clip((step - opt["warmup_steps"])
                    / max(1.0, opt["total_steps"] - opt["warmup_steps"]), 0.0, 1.0)
    frac = opt["min_lr_frac"]
    cos = frac + (1 - frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * jnp.where(step < opt["warmup_steps"], warm, cos)


def adamw(opt: dict, p, g, m, v, step):
    """One AdamW update; ``step`` counts from 1.  Returns (p, m, v)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    lr = lr_at(opt, step)
    c1, c2 = 1.0 - opt["b1"] ** step, 1.0 - opt["b2"] ** step
    sdt = jnp.dtype(opt["state_dtype"])

    def one(p, g, m, v):
        g = g * clip
        m1 = opt["b1"] * m.astype(F32) + (1 - opt["b1"]) * g
        v1 = opt["b2"] * v.astype(F32) + (1 - opt["b2"]) * g * g
        upd = (m1 / c1) / (jnp.sqrt(v1 / c2) + opt["eps"]) + opt["weight_decay"] * p.astype(F32)
        return (p.astype(F32) - lr * upd).astype(p.dtype), m1.astype(sdt), v1.astype(sdt)

    out = jax.tree.map(one, p, g, m, v)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], p, out)  # noqa: E731
    return pick(0), pick(1), pick(2)


def make_step(model: dict, mode: str = "fp32", devices=()):
    """(grad, update), jitted: grad(p, tokens, labels) -> (loss, float32
    gradients); update(p, g, m, v, step) -> (p, m, v).  Over several
    ``devices`` ``p`` is split as ``shardings`` says.  One pair per
    configuration, mode and devices in a process."""
    return _make_step(json.dumps(model, sort_keys=True), mode,
                      tuple(devices) if len(devices) > 1 else ())


@functools.lru_cache(maxsize=None)
def _make_step(model_json: str, mode: str, devices: tuple):
    model = json.loads(model_json)
    mm, exchange = MODES[mode]
    if not devices:
        if not exchange:
            raise ValueError(f"mode {mode!r} needs several devices")
        loss_fn = functools.partial(loss, model, mm)
    else:
        mesh = jax.sharding.Mesh(np.asarray(devices), (AXIS,))
        loss_fn = jax.shard_map(functools.partial(_loss_rows, model, mm, exchange), mesh=mesh,
                                in_specs=(leaf_specs(model, len(devices)), P(), P()),
                                out_specs=P())

    def grad(p, tokens, labels):
        p32 = jax.tree.map(lambda w: w.astype(F32), p)
        return jax.value_and_grad(loss_fn)(p32, tokens, labels)

    def update(p, g, m, v, n):
        return adamw(model["optimizer"], p, g, m, v, n)

    return jax.jit(grad), jax.jit(update, donate_argnums=(0, 2, 3))


# --------------------------------------------------------------------------- readings


@jax.jit
def leaf_norms(tree):
    """Float32 norm of every leaf, in the tree's flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(after, before):
    """Norm of each leaf's change, in float32."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
                      for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))])


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def trajectory(model: dict, batches: list, seed: int, mode: str = "fp32", devices=()):
    """The reference's readings over ``len(batches)`` steps from the seed's
    weights: each step's loss, each leaf's first gradient as the optimizer
    holds it (its first moment after step 1), and the final parameters.

    The moments wait in host memory while the gradients are computed, so
    that a 64k-token step of a 6.7B-wide model fits one chip.  Over several
    ``devices`` everything is split as ``shardings`` says."""
    grad, update = make_step(model, mode, devices)
    p = weights.make(model, seed, out_shardings=shardings(model, devices))
    where = jax.tree.map(lambda w: w.sharding, p)
    sdt = jnp.dtype(model["optimizer"]["state_dtype"])
    moments = jax.device_get(jax.tree.map(lambda w: jnp.zeros(w.shape, sdt, device=w.sharding),
                                          (p, p)))
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            val, g = grad(p, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
            p, m, v = update(p, g, *jax.device_put(moments, (where, where)), i + 1)
            del g
            losses.append(float(val))
            if i == 0:
                first = [float(x) for x in leaf_norms(m)]
            moments = jax.device_get((m, v))
            del m, v
    return {"losses": losses, "grad_norms": first, "params": p}
