"""Model assembly: scan-over-layers decoder covering all assigned families.

Layer stacking: layers are grouped into repeating *cycles* of the arch's
block pattern (dense/MoE/audio/vlm: 1-layer cycle; recurrentgemma:
(rglru, rglru, local_attn)); cycle parameters are stacked and the stack is
driven by one rematerialized ``lax.scan`` — the compiled HLO contains a
single cycle body regardless of depth (compile-time and HLO size stay flat
at 512 devices).  Remainder layers (38 % 3 == 2) run unrolled after the scan.
Inside each attention block the FPDT chunk pipeline is scan-compiled the
same way (core/fpdt.py), so HLO size is flat in the chunk count u as well;
``scan_layers=False`` (roofline probes) unrolls the layer stack.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import ad_checkpoint

from repro.configs import ModelConfig
from repro.core import fpdt
from repro.core.chunked_loss import IGNORE, auto_chunks, softmax_xent_chunked
from repro.core.parallel import ParallelContext, make_shard_fn
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rglru as R
from repro.runtime import placement

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(cfg: ModelConfig, kind: str, key, dtype) -> Params:
    ks = jax.random.split(key, 4)
    if kind in ("attn", "local_attn"):
        p = {
            "norm1": L.init_norm(cfg, dtype),
            "attn": L.init_attn(cfg, ks[0], dtype),
            "norm2": L.init_norm(cfg, dtype),
        }
        if cfg.num_experts:
            p["moe"] = MOE.init_moe(cfg, ks[1], dtype)
        else:
            p["mlp"] = L.init_mlp(cfg, ks[1], dtype)
        return p
    if kind == "ssm":
        return {"norm": L.init_norm(cfg, dtype), "mixer": M.init_mamba(cfg, ks[0], dtype)}
    if kind == "rglru":
        return {
            "norm1": L.init_norm(cfg, dtype),
            "mixer": R.init_rglru(cfg, ks[0], dtype),
            "norm2": L.init_norm(cfg, dtype),
            "mlp": L.init_mlp(cfg, ks[1], dtype),
        }
    raise ValueError(kind)


def pattern_of(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.block_pattern:
        return cfg.block_pattern
    return ("ssm",) if cfg.family == "ssm" else ("attn",)


def layout_of(cfg: ModelConfig):
    """(pattern, n_cycles, tail_kinds)."""
    pat = pattern_of(cfg)
    n_cycles = cfg.num_layers // len(pat)
    tail = tuple(pat[: cfg.num_layers % len(pat)])
    return pat, n_cycles, tail


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    pat, n_cycles, tail = layout_of(cfg)
    keys = jax.random.split(key, 4)
    params: Params = {}
    if cfg.frontend != "audio_frames":
        params["embed"] = (
            0.02 * jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model))
        ).astype(dtype)
    # stacked cycle params
    cyc_keys = jax.random.split(keys[1], n_cycles)

    def one_cycle(k):
        kk = jax.random.split(k, len(pat))
        return {f"pos{i}": _init_block(cfg, kind, kk[i], dtype) for i, kind in enumerate(pat)}

    params["cycles"] = jax.vmap(one_cycle)(cyc_keys)
    if tail:
        tk = jax.random.split(keys[2], len(tail))
        params["tail"] = [_init_block(cfg, kind, tk[i], dtype) for i, kind in enumerate(tail)]
    params["final_norm"] = L.init_norm(cfg, dtype)
    if not cfg.tie_embeddings:
        params["head"] = L._dense_init(keys[3], (cfg.d_model, cfg.padded_vocab), dtype)
    return params


def head_matrix(cfg: ModelConfig, params: Params) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


def attn_kind(cfg: ModelConfig, par: Optional[ParallelContext]) -> str:
    if par is None or par.mesh is None:
        return "local"
    if cfg.attn_impl in ("ulysses", "cp"):
        return cfg.attn_impl
    return "ulysses" if cfg.num_heads % par.sp == 0 else "cp"


# ---------------------------------------------------------------------------
# training / prefill forward
# ---------------------------------------------------------------------------


def block_apply(cfg: ModelConfig, par: Optional[ParallelContext], kind: str,
                p: Params, h: jnp.ndarray, pos_offset: int = 0):
    """One block; returns (h, aux_loss)."""
    shard = make_shard_fn(par)
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else 0
        hn = L.apply_norm(cfg, p["norm1"], h)
        o = fpdt.fpdt_attention(cfg, par, p["attn"], hn,
                                kind=attn_kind(cfg, par), window=window,
                                pos_offset=pos_offset)
        h = h + o @ p["attn"]["wo"]
        hn2 = L.apply_norm(cfg, p["norm2"], h)
        if cfg.num_experts:
            y, aux = MOE.moe_ffn_chunked(cfg, p["moe"], hn2, cfg.mlp_chunks, shard)
        else:
            y, aux = L.mlp_chunked(cfg, p["mlp"], hn2, cfg.mlp_chunks), jnp.float32(0)
        return h + y, aux
    if kind == "ssm":
        hn = L.apply_norm(cfg, p["norm"], h)
        y, _ = M.mamba_mixer(cfg, p["mixer"], hn, None, shard,
                             n_shards=par.sp if par is not None and par.mesh is not None else 1)
        return h + y, jnp.float32(0)
    if kind == "rglru":
        hn = L.apply_norm(cfg, p["norm1"], h)
        y, _ = R.rglru_mixer(cfg, p["mixer"], hn, None, shard,
                             scan_impl="pallas" if par is None else "xla",
                             n_shards=par.sp if par is not None and par.mesh is not None else 1)
        h = h + y
        hn2 = L.apply_norm(cfg, p["norm2"], h)
        return h + L.mlp_chunked(cfg, p["mlp"], hn2, cfg.mlp_chunks), jnp.float32(0)
    raise ValueError(kind)


def _remat_policy(cfg: ModelConfig, par: Optional[ParallelContext] = None):
    if cfg.remat == "none":
        return None
    if cfg.remat == "offload":
        # memory kinds come from the placement layer; on backends with no
        # host pool this degrades to full remat (nothing saveable)
        pol = par.pol if par is not None else placement.default_policy()
        return pol.remat_policy(offload_names=["block_in"])
    return jax.checkpoint_policies.nothing_saveable


def hidden_forward(cfg: ModelConfig, par: Optional[ParallelContext],
                   params: Params, h: jnp.ndarray):
    """Run the full layer stack. h: [b, S, d]. Returns (h, aux)."""
    pat, n_cycles, tail = layout_of(cfg)
    if par is not None and par.mesh is not None:
        h = par.seq_sharded(h)

    def cycle_body(carry, cyc_p):
        x, aux = carry
        if cfg.remat != "none":
            x = ad_checkpoint.checkpoint_name(x, "block_in")
        for i, kind in enumerate(pat):
            x, a = block_apply(cfg, par, kind, cyc_p[f"pos{i}"], x)
            aux = aux + a
        if par is not None and par.mesh is not None:
            x = par.seq_sharded(x)
        return (x, aux), None

    body = cycle_body
    if cfg.remat != "none":
        body = jax.checkpoint(cycle_body, policy=_remat_policy(cfg, par),
                              prevent_cse=False)
    if cfg.scan_layers:
        (h, aux), _ = jax.lax.scan(body, (h, jnp.float32(0)), params["cycles"])
    else:  # unrolled (roofline probes: HLO costs scale with true layer count)
        carry = (h, jnp.float32(0))
        for ci in range(n_cycles):
            cyc = jax.tree.map(lambda x: x[ci], params["cycles"])
            carry, _ = body(carry, cyc)
        h, aux = carry
    for i, kind in enumerate(tail):
        h, a = block_apply(cfg, par, kind, params["tail"][i], h)
        aux = aux + a
    return h, aux


def embed_input(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray]):
    """Assemble the input hidden sequence (modality frontends are stubs)."""
    if cfg.frontend == "audio_frames":
        h = batch["frame_embeds"]  # [b, s, d] precomputed EnCodec frame embeds
        s = h.shape[1]
        h = h + L.sinusoidal_pos_emb(s, cfg.d_model).astype(h.dtype)[None]
        return h
    tok_emb = params["embed"][batch["tokens"]]
    if cfg.frontend == "vision_patches":
        return jnp.concatenate([batch["patch_embeds"].astype(tok_emb.dtype), tok_emb], axis=1)
    return tok_emb


def loss_fn(cfg: ModelConfig, par: Optional[ParallelContext],
            params: Params, batch: Dict[str, jnp.ndarray]):
    """Mean next-token xent (labels pre-shifted; IGNORE masked). Returns
    (loss, metrics)."""
    h = embed_input(cfg, params, batch)
    h = h.astype(jnp.dtype(cfg.param_dtype))
    h, aux = hidden_forward(cfg, par, params, h)
    h = L.apply_norm(cfg, params["final_norm"], h)
    labels = batch["labels"]
    if cfg.frontend == "vision_patches":  # no loss on patch positions
        pad = jnp.full(batch["patch_embeds"].shape[:2], IGNORE, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    n_chunks = cfg.loss_chunks or auto_chunks(
        cfg, h.shape[1], sp=par.sp if par is not None else 1)
    with jax.named_scope("loss_head"):
        loss_sum, count = softmax_xent_chunked(h, head_matrix(cfg, params), labels, n_chunks,
                                               par=par)
    loss = loss_sum / jnp.maximum(count, 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux, "tokens": count}
