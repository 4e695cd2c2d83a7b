"""Serving: prefill + incremental decode == full forward recompute, and the
scan-compiled decode engine (`runtime/decode_loop.py`) == the per-token
reference loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.parallel import ParallelContext
from repro.models import layers as L
from repro.models import serve as SV
from repro.models import transformer as T
from repro.runtime import decode_loop as DL


def full_logits(cfg, params, batch):
    h = T.embed_input(cfg, params, batch).astype(jnp.dtype(cfg.param_dtype))
    h, _ = T.hidden_forward(cfg, None, params, h)
    h = L.apply_norm(cfg, params["final_norm"], h)
    return (h @ T.head_matrix(cfg, params)).astype(jnp.float32)


ARCHS = ["llama3.2-1b", "qwen1.5-4b", "falcon-mamba-7b", "recurrentgemma-9b",
         "granite-moe-1b-a400m", "musicgen-medium", "internvl2-2b"]


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_decode_match_full(name):
    cfg = dataclasses.replace(reduced(get_config(name)), param_dtype="float32",
                              remat="none", moe_capacity_factor=100.0)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    b, s = 2, 16
    if cfg.frontend == "audio_frames":
        fe = jax.random.normal(key, (b, s + 1, cfg.d_model), jnp.float32)
        pre_b, dec_i = {"frame_embeds": fe[:, :s]}, {"frame_embeds": fe[:, s:s + 1]}
        full_b, full_b1 = pre_b, {"frame_embeds": fe}
    elif cfg.frontend == "vision_patches":
        st = s - cfg.num_patches
        pe = jax.random.normal(key, (b, cfg.num_patches, cfg.d_model), jnp.float32)
        toks = jax.random.randint(key, (b, st + 1), 0, cfg.vocab_size)
        pre_b = {"patch_embeds": pe, "tokens": toks[:, :st]}
        dec_i = {"tokens": toks[:, st:st + 1]}
        full_b, full_b1 = pre_b, {"patch_embeds": pe, "tokens": toks}
    else:
        toks = jax.random.randint(key, (b, s + 1), 0, cfg.vocab_size)
        pre_b, dec_i = {"tokens": toks[:, :s]}, {"tokens": toks[:, s:s + 1]}
        full_b, full_b1 = pre_b, {"tokens": toks}
    logits_pre, cache = SV.prefill_step(cfg, None, params, pre_b, max_len=32)
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(full_logits(cfg, params, full_b)[:, s - 1]),
                               rtol=2e-3, atol=2e-3)
    logits_dec, _ = SV.decode_step(cfg, None, params, cache, dec_i, jnp.int32(s))
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(full_logits(cfg, params, full_b1)[:, s]),
                               rtol=2e-3, atol=2e-3)


def test_host_chunked_decode_matches_plain():
    """FPDT-for-inference: host-streamed KV == on-device KV decode."""
    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")),
                              param_dtype="float32", remat="none")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    _, cache = SV.prefill_step(cfg, None, params, {"tokens": toks[:, :16]}, max_len=32)
    l0, _ = SV.decode_step(cfg, None, params, cache, {"tokens": toks[:, 16:17]}, jnp.int32(16))
    par = ParallelContext(mesh=None)
    l8, _ = SV.decode_step(cfg, par, params, cache, {"tokens": toks[:, 16:17]},
                           jnp.int32(16), n_host_chunks=8)
    np.testing.assert_allclose(np.asarray(l8), np.asarray(l0), rtol=1e-4, atol=1e-4)


def test_dense_host_chunks_refused_on_accelerator():
    """The dense cache is never host-parked, so host-chunked attention over
    it would be offload silently not done on an accelerator: it raises."""
    from repro.runtime.placement import PlacementPolicy

    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), remat="none")
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: SV.init_cache(cfg, 1, 16))
    tpu = PlacementPolicy(device_kind="device", host_kind="pinned_host", backend="tpu")
    par = ParallelContext(mesh=None, placement=tpu)
    with pytest.raises(NotImplementedError, match="PagedServeEngine"):
        jax.eval_shape(lambda p, c: SV.decode_step(
            cfg, par, p, c, {"tokens": jnp.zeros((1, 1), jnp.int32)}, 3,
            n_host_chunks=2), params, cache)


def _per_token_loop(cfg, par, params, cache, tok0, pos0, steps, n_host_chunks=0):
    """Reference: one decode_step dispatch per token, greedy."""
    outs, logits_all = [tok0], []
    for i in range(steps):
        l, cache = SV.decode_step(cfg, par, params, cache,
                                  {"tokens": outs[-1][:, None]},
                                  jnp.int32(pos0 + i), n_host_chunks=n_host_chunks)
        logits_all.append(l[:, : cfg.vocab_size])
        outs.append(jnp.argmax(l[:, : cfg.vocab_size], -1).astype(jnp.int32))
    return jnp.stack(outs[1:], 1), jnp.stack(logits_all, 0)


@pytest.mark.parametrize("name,chunks", [
    ("llama3.2-1b", 0), ("llama3.2-1b", 4),        # attn, on-device + host-KV
    ("falcon-mamba-7b", 0), ("recurrentgemma-9b", 0),  # ssm / rglru+local_attn
])
def test_scan_decode_matches_per_token_loop(name, chunks):
    """decode_tokens (one lax.scan) == per-token loop: logits AND greedy ids."""
    cfg = dataclasses.replace(reduced(get_config(name)), param_dtype="float32",
                              remat="none")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    b, s, steps = 2, 8, 5
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    logits, cache = SV.prefill_step(cfg, None, params, {"tokens": toks}, max_len=16)
    tok0 = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    par = ParallelContext(mesh=None) if chunks else None
    want_ids, want_logits = _per_token_loop(cfg, par, params, cache, tok0, s,
                                            steps, n_host_chunks=chunks)
    got_ids, aux = DL.decode_tokens(cfg, par, params, cache, tok0[:, None],
                                    jnp.full((b,), s, jnp.int32), num_steps=steps,
                                    n_host_chunks=chunks, collect_logits=True)
    assert got_ids.tolist() == want_ids.tolist()
    np.testing.assert_allclose(np.asarray(aux["logits"]), np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)


def test_position_masked_prefill_matches_exact():
    """Right-padded prefill with lengths == exact-length prefill, per row."""
    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")),
                              param_dtype="float32", remat="none")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)
    lengths = [5, 9]
    l_pad, cache = SV.prefill_step(cfg, None, params, {"tokens": toks}, max_len=16,
                                   lengths=jnp.asarray(lengths, jnp.int32))
    for i, n in enumerate(lengths):
        l_exact, _ = SV.prefill_step(cfg, None, params,
                                     {"tokens": toks[i:i + 1, :n]}, max_len=16)
        np.testing.assert_allclose(np.asarray(l_pad[i]), np.asarray(l_exact[0]),
                                   rtol=2e-4, atol=2e-4)
    # padded slots must be masked out of the cache
    kpos = cache["pos0"]["kpos"]  # [C, b, s]
    assert (np.asarray(kpos[:, 0, 5:]) == -1).all()
    # recurrent layouts must refuse (their states integrate pad tokens)
    with pytest.raises(ValueError, match="position-masked"):
        SV.prefill_step(dataclasses.replace(reduced(get_config("falcon-mamba-7b")),
                                            param_dtype="float32", remat="none"),
                        None, T.init_params(reduced(get_config("falcon-mamba-7b")),
                                            jax.random.PRNGKey(0)),
                        {"tokens": toks}, max_len=16,
                        lengths=jnp.asarray(lengths, jnp.int32))


def test_continuous_batching_staggered_finishes():
    """ServeEngine (slots < requests, mixed lengths, stop token firing at
    different steps) reproduces per-prompt solo greedy generation."""
    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")),
                              param_dtype="float32", remat="none")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (3, 8, 5, 6)]
    max_new = 6

    def solo(prompt):
        t = jnp.asarray([prompt], jnp.int32)
        logits, cache = SV.prefill_step(cfg, None, params, {"tokens": t},
                                        max_len=8 + max_new)
        tok0 = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        ids, _ = _per_token_loop(cfg, None, params, cache, tok0, len(prompt),
                                 max_new - 1)
        return [int(tok0[0])] + [int(t) for t in ids[0]]

    solos = [solo(p) for p in prompts]
    stop = solos[0][2]  # fires at step 3 for prompt 0; elsewhere (if at all) later

    def trunc(g):
        return g[: g.index(stop) + 1] if stop in g else g

    eng = DL.ServeEngine(cfg, params, slots=2, bucket=8, max_new_tokens=max_new,
                         segment=2, stop_tokens=(stop,))
    got = eng.generate(prompts)
    want = [trunc(g) for g in solos]
    assert got == want
    assert len({len(g) for g in want}) > 1  # finishes genuinely staggered

    # a stop token sampled directly from prefill logits (before any scan
    # step) must also finish the sequence
    stop0 = solos[2][0]

    def trunc0(g):
        return g[: g.index(stop0) + 1] if stop0 in g else g

    eng0 = DL.ServeEngine(cfg, params, slots=2, bucket=8, max_new_tokens=max_new,
                          segment=2, stop_tokens=(stop0,))
    got0 = eng0.generate(prompts)
    assert got0 == [trunc0(g) for g in solos]
    assert len(got0[2]) == 1  # prompt 2 stopped on its very first token


def test_continuous_batching_recurrent_full_bucket():
    """Recurrent layouts can use the engine when prompts exactly fill the
    bucket (no pads -> unmasked prefill): engine == solo generation."""
    cfg = dataclasses.replace(reduced(get_config("falcon-mamba-7b")),
                              param_dtype="float32", remat="none")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    bucket, max_new = 6, 4
    prompts = [rng.integers(0, cfg.vocab_size, size=bucket).tolist()
               for _ in range(3)]

    def solo(prompt):
        t = jnp.asarray([prompt], jnp.int32)
        logits, cache = SV.prefill_step(cfg, None, params, {"tokens": t},
                                        max_len=bucket + max_new)
        tok0 = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        ids, _ = _per_token_loop(cfg, None, params, cache, tok0, bucket,
                                 max_new - 1)
        return [int(tok0[0])] + [int(t) for t in ids[0]]

    eng = DL.ServeEngine(cfg, params, slots=2, bucket=bucket,
                         max_new_tokens=max_new, segment=3)
    assert eng.generate(prompts) == [solo(p) for p in prompts]


def test_greedy_decode_loop():
    """Multi-step greedy decode is self-consistent with a one-shot forward."""
    cfg = dataclasses.replace(reduced(get_config("llama3.2-1b")),
                              param_dtype="float32", remat="none")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    logits, cache = SV.prefill_step(cfg, None, params, {"tokens": toks}, max_len=32)
    out = [int(jnp.argmax(logits[:, :cfg.vocab_size], -1)[0])]
    pos = 8
    for _ in range(4):
        logits, cache = SV.decode_step(
            cfg, None, params, cache,
            {"tokens": jnp.asarray([[out[-1]]], jnp.int32)}, jnp.int32(pos))
        out.append(int(jnp.argmax(logits[:, :cfg.vocab_size], -1)[0]))
        pos += 1
    # oracle: rerun full forward over the realized sequence
    seq = jnp.concatenate([toks, jnp.asarray([out[:-1]], jnp.int32)], axis=1)
    fl = full_logits(cfg, params, {"tokens": seq})
    want = [int(jnp.argmax(fl[0, i, :cfg.vocab_size])) for i in range(7, 12)]
    assert out == want
