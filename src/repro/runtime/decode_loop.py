"""Scan-compiled multi-token decode engine (FPDT-style serving).

``models/serve.py`` owns the single-step primitives (prefill, one-token
decode against the cache); this module owns the *loop*:

* ``decode_tokens`` — ONE ``lax.scan`` over generation steps.  The decode
  body (a full layer-cycle scan, optionally with host-chunked KV streaming)
  is traced once, so program size is flat in the number of generated tokens
  — the per-token Python loop it replaces re-dispatched a jitted call per
  token and paid host latency on every step.  Greedy and temperature/top-k
  sampling, per-sequence stop-token and budget handling.
* ``ServeEngine`` — continuous batching via the **fused mixed-step
  scheduler**: ONE compiled program per step that, for every cache slot,
  either consumes one prefill chunk or decodes one token, selected by a
  per-slot traced state machine (``FREE / PREFILL / DECODE``) carried
  through the scan — so a refilling slot's prompt streams in
  chunk-by-chunk *under* the other slots' decode steps (ChunkFlow-style,
  the serving-side dual of the FPDT sequence-chunk pipeline), and prompts
  longer than the bucket are legal (they just take more chunks).
* ``BlockingServeEngine`` — the PR 3 three-program engine (batched
  prefill, decode segment, synchronous single-row refill prefill), kept
  as the measured stall baseline for ``benchmarks/serve_bench.py``.

Measured by ``benchmarks/serve_bench.py``; architecture notes in
``docs/serving.md``.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.core.parallel import ParallelContext
from repro.models import serve as SV
from repro.runtime import telemetry as TM

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """``temperature <= 0`` selects greedy argmax (the default); otherwise
    categorical sampling at the given temperature, optionally restricted to
    the ``top_k`` highest-probability tokens (0 = full vocabulary).

    Frozen + hashable so it can close over a jitted decode loop."""

    temperature: float = 0.0
    top_k: int = 0


GREEDY = SamplingConfig()


def sample_token(logits: jnp.ndarray, key, sc: SamplingConfig = GREEDY) -> jnp.ndarray:
    """logits [b, V] fp32 -> sampled token ids [b] int32."""
    if sc.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if sc.top_k:
        kth = jax.lax.top_k(logits, sc.top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits / sc.temperature, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# requests (QoS contract for the SLO-aware scheduler)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Request:
    """One queued generation request plus its QoS contract.

    Plain prompts (token sequences) coerce to default requests via
    :func:`as_request`, so every engine ``generate`` keeps accepting raw
    token lists.  The extra fields only matter to the SLO-aware scheduler
    (``runtime/paged.py::SLOPagedServeEngine``); the FIFO engines ignore
    them:

      priority       — admission class, LOWER value = more urgent (0 =
                       interactive tier).  The SLO scheduler admits
                       strictly by (priority, itl_slo) and may preempt a
                       decoding request to seat a strictly more urgent
                       one;
      arrival        — the dispatch step at which the request becomes
                       visible to the scheduler (the traffic simulator's
                       deterministic clock; 0 = already queued);
      itl_slo        — inter-token-latency deadline in dispatch steps
                       (the tie-break within a priority class: tightest
                       deadline first, EDF-style).  ``inf`` = no deadline;
      prefill_chunks — per-request prefill budget: at most this many
                       prefill chunks per burst before the scheduler
                       pauses the prefill for one segment so co-resident
                       decodes get a chunk-free (fast-path) step
                       (0 = engine default / unlimited);
      tier           — free-form label carried into per-request stats
                       (the benchmark's goodput-under-SLO accounting);
      session        — routing-affinity id read by ``launch/router.py``
                       (requests of one session hash to one replica, and
                       re-home together on replica death); ``None`` =
                       route by prompt-prefix hash.  Engines ignore it.
    """

    tokens: Tuple[int, ...]
    priority: int = 1
    arrival: int = 0
    itl_slo: float = math.inf
    prefill_chunks: int = 0
    tier: str = ""
    session: Optional[str] = None


def as_request(r: Union[Request, Sequence[int]]) -> Request:
    """Coerce a raw prompt (token sequence) into a default :class:`Request`;
    pass real requests through untouched."""
    if isinstance(r, Request):
        return r
    return Request(tokens=tuple(int(t) for t in r))


# ---------------------------------------------------------------------------
# scan-compiled multi-token decode
# ---------------------------------------------------------------------------


def decode_tokens(cfg: ModelConfig, par: Optional[ParallelContext], params: Params,
                  cache: Params, tok: jnp.ndarray, pos: jnp.ndarray, *,
                  num_steps: int, n_host_chunks: int = 0,
                  sampling: SamplingConfig = GREEDY,
                  stop_tokens: Sequence[int] = (), pad_id: int = 0,
                  key: Optional[jnp.ndarray] = None,
                  done: Optional[jnp.ndarray] = None,
                  remaining: Optional[jnp.ndarray] = None,
                  collect_logits: bool = False):
    """Generate up to ``num_steps`` tokens per sequence with one ``lax.scan``.

    Carry contract (shape/dtype-stable across steps, scan-compatible):
      cache      — decode cache pytree (``models/serve.py`` layouts);
      tok [b,1]  — the token each sequence feeds NEXT.  The caller samples
                   the first token from the prefill logits, so the full
                   generation is ``[tok0, *emitted]``;
      pos [b]    — the position ``tok`` occupies; frozen once a row is done;
      key        — PRNG carry (split every step; unused under greedy);
      done [b]   — finished rows emit ``pad_id``, stop advancing ``pos``,
                   and stop consuming budget.  Their dummy decode writes
                   land at the frozen ``pos`` slot, which is rewritten by
                   the next prefill when the slot is re-used;
      remaining [b] — per-row emission budget; a row finishes after
                   emitting ``remaining`` tokens or a ``stop_tokens`` hit
                   (the stop token itself is emitted).

    Step t feeds ``tok`` at ``pos``, samples from the resulting logits, and
    emits the SAMPLED token — identical to the per-token loop
    ``outs.append(sample(decode(cache, outs[-1], pos)))``.

    Returns ``(tokens [b, num_steps] int32, aux)`` with
    ``aux = {cache, tok, pos, key, done, remaining[, logits]}`` — exactly
    the carry, so segments chain: feed ``aux`` back in to continue (the
    continuous-batching engine decodes in segments and harvests/refills
    between them).  ``aux["remaining"]`` deltas give per-row emission
    counts; ``collect_logits`` adds the per-step pre-sampling logits
    ``[num_steps, b, vocab]`` (parity tests only — it scales with vocab).
    """
    if cfg.frontend == "audio_frames":
        raise ValueError("decode_tokens feeds token ids; the audio_frames "
                         "frontend consumes frame embeddings — drive "
                         "decode_step directly for frame synthesis")
    b = tok.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    key = jax.random.PRNGKey(0) if key is None else key
    done = jnp.zeros((b,), bool) if done is None else done
    if remaining is None:
        remaining = jnp.full((b,), num_steps + 1, jnp.int32)
    remaining = jnp.asarray(remaining, jnp.int32)
    done = done | (remaining <= 0)
    stop = jnp.asarray(tuple(stop_tokens), jnp.int32)

    def step(carry, _):
        cache, tok, pos, key, was_done, rem = carry
        key, sub = jax.random.split(key)
        logits, cache = SV.decode_step(cfg, par, params, cache, {"tokens": tok},
                                       pos, n_host_chunks=n_host_chunks)
        lv = logits[:, : cfg.vocab_size]
        nxt = sample_token(lv, sub, sampling)
        rem = rem - jnp.where(was_done, 0, 1)
        emit = jnp.where(was_done, pad_id, nxt)  # the stop token itself is emitted
        done = was_done | jnp.isin(nxt, stop) | (rem <= 0)
        pos = jnp.where(was_done, pos, pos + 1)
        return (cache, emit[:, None], pos, key, done, rem), (
            emit, lv if collect_logits else None)

    carry0 = (cache, tok.astype(jnp.int32), pos, key, done, remaining)
    (cache, tok, pos, key, done, remaining), (toks, logits) = jax.lax.scan(
        step, carry0, None, length=num_steps)
    aux = {"cache": cache, "tok": tok, "pos": pos, "key": key,
           "done": done, "remaining": remaining}
    if collect_logits:
        aux["logits"] = logits
    return toks.T, aux


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


def _batch_axis(path) -> int:
    """Batch-dim axis of a cache leaf: stacked cycle leaves are [C, b, ...],
    tail leaves [b, ...] (mirrors ``SV.cache_shardings``)."""
    names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
    return 0 if names[0] == "tail" else 1


def insert_slot(cache: Params, one: Params, i) -> Params:
    """Write a single-sequence (b=1) cache ``one`` into batch slot ``i`` of
    ``cache`` — the slot-reuse primitive of continuous batching."""
    def put(path, cb, c1):
        return jax.lax.dynamic_update_slice_in_dim(
            cb, c1.astype(cb.dtype), i, axis=_batch_axis(path))

    return jax.tree_util.tree_map_with_path(put, cache, one)


def reset_slot(cache: Params, i) -> Params:
    """Invalidate batch slot ``i`` before chunked prefill streams a new
    prompt into it: ``kpos`` rows go to -1 (no stale attention entries can
    leak into the new sequence — chunk writes only cover the new prompt's
    positions, unlike the old full-row ``insert_slot`` refill) and
    recurrent state rows (conv/ssm/h) go to 0.  k/v payloads stay — they
    are unreachable once ``kpos`` is -1."""

    def fix(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        kind = names[-1]
        if kind not in ("kpos", "conv", "ssm", "h"):
            return leaf
        ax = _batch_axis(path)
        shape = list(leaf.shape)
        shape[ax] = 1
        fill = -1 if kind == "kpos" else 0
        row = jnp.full(shape, fill, leaf.dtype)
        return jax.lax.dynamic_update_slice_in_dim(leaf, row, i, axis=ax)

    return jax.tree_util.tree_map_with_path(fix, cache)


# per-slot scheduler states (traced int32)
FREE, PREFILL, DECODE = 0, 1, 2


def per_engine(fn, telemetry: Optional[TM.Telemetry] = None,
               name: Optional[str] = None):
    """Per-engine jit identity wrapper.  ``jax.jit``'s dispatch cache is
    global, keyed by (function, jit params): two engines built with EQUAL
    shardings over the same module-level function would pool their compile
    counts, corrupting the ``compiled_programs()`` bounded-set accounting
    (an engine would "inherit" another engine's compilations).  Wrapping
    in a fresh function object keeps the count engine-local.

    With a ``telemetry``, the wrapper doubles as the compile probe: the
    wrapped python function only executes while jax *traces* — i.e. once
    per new compiled program — so each call records one ``compile.<name>``
    event, and growth past the engine's bounded-program budget surfaces
    as a telemetry alert instead of only a slow-test assert."""
    label = name or fn.__name__

    def wrapped(*args):
        if telemetry is not None:
            telemetry.compile_event(label)
        return fn(*args)

    wrapped.__name__ = getattr(fn, "__name__", label)
    return wrapped


def mixed_segment(cfg: ModelConfig, par: Optional[ParallelContext], params: Params,
                  cache: Params, mode: jnp.ndarray, tok: jnp.ndarray,
                  pos: jnp.ndarray, key: jnp.ndarray, rem: jnp.ndarray,
                  pfill: jnp.ndarray, pend: jnp.ndarray, plen: jnp.ndarray, *,
                  num_steps: int, prefill_chunk: int, n_host_chunks: int = 0,
                  sampling: SamplingConfig = GREEDY,
                  stop_tokens: Sequence[int] = (), pad_id: int = 0,
                  table: Optional[jnp.ndarray] = None):
    """Run ``num_steps`` fused mixed steps in ONE ``lax.scan``.

    Per step, each slot does what its traced state says:
      PREFILL — consume the next ``prefill_chunk``-token chunk of its
                pending prompt (``chunk_step`` at offset ``pfill``; the
                final partial chunk is position-masked and recurrent state
                is gathered at the true length).  When the prompt is
                exhausted the slot samples its first token from the chunk
                logits, emits it, and transitions to DECODE (or straight
                to FREE on a stop token / empty budget);
      DECODE  — decode one token (emit, advance ``pos``, burn budget;
                stop token or exhausted budget -> FREE);
      FREE    — no-op (live=0 in the chunk program: nothing is written).

    The step is ONE compiled program: a ``lax.cond`` between the unified
    chunk program (any slot prefilling — decode slots ride it as live=1
    windows) and the plain ``decode_step`` fast path (nobody prefilling —
    steady-state decode pays zero chunk overhead).  Both branches are
    traced once, so program size is flat in chunk length, cache capacity,
    and step count.

    Carry (shape/dtype-stable): ``(cache, mode, tok, pos, key, rem,
    pfill)``; ``pend [b, P]``/``plen [b]`` (the staged prompts) and the
    optional paged-pool page ``table`` ([b, max_pages] int32, see
    ``runtime/paged.py`` — threaded into both step bodies so attention
    gathers/scatters K/V through it) are scan-invariant.  Returns
    ``(emit [b, num_steps], valid [b, num_steps], aux)`` where ``aux`` is
    the final carry as a dict — segments chain by feeding it back, and
    the host harvests ``emit`` where ``valid``.
    """
    b = tok.shape[0]
    cp = int(prefill_chunk)
    P = pend.shape[1]
    if n_host_chunks and table is not None:
        # the paged pool's K/V stay host-side: give them their memory
        # space before they enter the scan carry
        cache = SV.park_pool(par, cache)
    stop = jnp.asarray(tuple(stop_tokens), jnp.int32)
    V = cfg.vocab_size

    def step(carry, _):
        cache, mode, tok, pos, key, rem, pfill = carry
        key, sub = jax.random.split(key)
        is_pf = mode == PREFILL

        def chunk_branch(cache, tok):
            off = jnp.where(is_pf, pfill, pos)
            live = jnp.where(is_pf, jnp.clip(plen - pfill, 0, cp),
                             jnp.where(mode == DECODE, 1, 0))
            idx = jnp.clip(off[:, None] + jnp.arange(cp)[None, :], 0, P - 1)
            toks = jnp.take_along_axis(pend, idx, axis=1)
            toks = jnp.where(is_pf[:, None], toks, tok)  # decode rows: col 0 = tok
            return SV.chunk_step(cfg, par, params, cache, toks, off, live,
                                 n_host_chunks=n_host_chunks, table=table)

        def decode_branch(cache, tok):
            return SV.decode_step(cfg, par, params, cache, {"tokens": tok},
                                  pos, n_host_chunks=n_host_chunks, table=table)

        logits, cache = jax.lax.cond(jnp.any(is_pf), chunk_branch,
                                     decode_branch, cache, tok)
        nxt = sample_token(logits[:, :V], sub, sampling)
        pfill = jnp.where(is_pf, jnp.minimum(pfill + cp, plen), pfill)
        fin_pf = is_pf & (pfill >= plen)  # prompt exhausted THIS step
        is_dec = mode == DECODE
        emitting = is_dec | fin_pf
        valid = emitting & (rem > 0)
        emit = jnp.where(valid, nxt, pad_id)
        rem = rem - valid.astype(jnp.int32)
        hit_stop = valid & jnp.isin(nxt, stop)
        now_free = emitting & (hit_stop | (rem <= 0))
        mode = jnp.where(now_free, FREE, jnp.where(fin_pf, DECODE, mode))
        pos = jnp.where(fin_pf, plen,
                        jnp.where(is_dec & ~now_free, pos + 1, pos))
        tok = jnp.where(emitting, nxt, tok[:, 0])[:, None]
        return (cache, mode, tok, pos, key, rem, pfill), (emit, valid)

    carry0 = (cache, jnp.asarray(mode, jnp.int32), tok.astype(jnp.int32),
              jnp.asarray(pos, jnp.int32), key, jnp.asarray(rem, jnp.int32),
              jnp.asarray(pfill, jnp.int32))
    (cache, mode, tok, pos, key, rem, pfill), (emits, valids) = jax.lax.scan(
        step, carry0, None, length=num_steps)
    aux = {"cache": cache, "mode": mode, "tok": tok, "pos": pos, "key": key,
           "rem": rem, "pfill": pfill}
    return emits.T, valids.T, aux


def segment_shardings(cfg, par: Optional[ParallelContext], cache, *,
                      table: bool = False, host_pool: bool = False):
    """``(in_shardings, out_shardings)`` for the mixed-segment jit on a
    mesh, or ``None`` off-mesh.

    The cache pytree follows ``models/serve.py::cache_shardings`` (paged
    pool kv-heads over ``model``, per-slot rows over ``data``); every
    scheduler scalar/row (mode/tok/pos/key/rem/pfill/pend/plen, and the
    page ``table`` when present) is explicitly replicated — explicit
    ``par.ns()`` rather than ``None`` so jit never has to guess.
    ``NamedSharding`` is shape-free, so ONE jitted segment still serves
    every workload capacity: the exactly-2-programs guarantee survives
    meshing.  ``cache`` may be real arrays or ``jax.eval_shape`` structs —
    only shapes are read.

    ``host_pool``: the paged pool's ``pk``/``pv`` enter in the offload
    pool (their own memory kind) and ``out_shardings`` is ``None``: with a
    host memory kind among its arguments jit would annotate the placement
    of every explicitly-sharded output, and the SPMD partitioner refuses
    those annotations on replicated outputs.  The engine puts the returned
    cache back onto the in-shardings."""
    if par is None or par.mesh is None:
        return None
    csh = SV.cache_shardings(cfg, par, cache, host_pool=host_pool)
    r = par.ns()
    # trailing argument: the weights, replicated (one prefix for the pytree)
    in_sh = (csh,) + (r,) * (8 + (1 if table else 0)) + (r,)
    if host_pool:
        return in_sh, None
    out_sh = (r, r, {"cache": csh, "mode": r, "tok": r, "pos": r, "key": r,
                     "rem": r, "pfill": r})
    return in_sh, out_sh


class ServeEngine:
    """Continuous batching over ``slots`` concurrent cache rows, scheduled
    by the fused mixed step (``mixed_segment``).

    Queued prompts are staged into a per-slot pending buffer and streamed
    into the cache chunk-by-chunk (``prefill_chunk`` tokens per step)
    *while the other slots keep decoding* — refill never stops the world,
    and any layout joins variable-length continuous batching (recurrent
    blocks ride the state-at-length gather; see ``models/serve.py``).
    Prompts of any length > 0 are accepted: the pending buffer and cache
    capacity derive from ``max(bucket, longest prompt)``, so ``bucket`` is
    the floor that keeps program shapes stable across calls, not a limit.

    Exactly TWO compiled programs regardless of workload mix: the mixed
    segment (one ``lax.scan`` of fused steps) and ``reset_slot`` (row
    invalidation at assignment) — ``compiled_programs()`` reports the live
    count so tests can pin it.

    The slot-lifecycle points are overridable hooks (``_begin`` /
    ``_admit`` / ``_dispatch`` / ``_post_dispatch`` / ``_release`` /
    ``_end``) so ``runtime/paged.py::PagedServeEngine`` can swap the dense
    per-slot cache for the slot-shared paged pool without touching the
    scheduler itself.  An ``_admit`` override may dispatch extra
    cache-maintenance work from its admit plan (page invalidation, COW
    copies, promote-from-spill scatters) — each through ONE jitted
    program, so the compiled set stays bounded at segment + reset (+ the
    paged engine's copy and promote).
    """

    def __init__(self, cfg: ModelConfig, params: Params, *, slots: int,
                 bucket: int, max_new_tokens: int, prefill_chunk: int = 0,
                 n_host_chunks: int = 0, sampling: SamplingConfig = GREEDY,
                 stop_tokens: Sequence[int] = (), pad_id: int = 0,
                 segment: int = 8, par: Optional[ParallelContext] = None):
        self.cfg, self.params, self.par = cfg, params, par
        self.slots, self.bucket = slots, bucket
        self.max_new = max_new_tokens
        self.sampling, self.pad_id = sampling, pad_id
        self.segment = segment
        self.n_host_chunks = n_host_chunks
        self.cp = int(prefill_chunk) if prefill_chunk else min(bucket, 64)
        self._stop = tuple(stop_tokens)
        self.telemetry = TM.Telemetry(component="engine")
        self.last_stats: Dict[str, Any] = self.telemetry.stats_view()
        self._build_programs()

    # -- compiled programs (subclass hook) -------------------------------
    def _segment_shardings(self):
        """``segment_shardings`` over a representative cache, or ``None``
        off-mesh.  Bucket-capacity shapes stand in for every workload —
        ``NamedSharding`` carries no shape, so the sharded jit still serves
        all capacities with the same two programs."""
        if self.par is None or self.par.mesh is None:
            return None
        _, S = self._capacity([[0]])
        cache = jax.eval_shape(lambda: SV.init_cache(self.cfg, self.slots, S))
        return segment_shardings(self.cfg, self.par, cache)

    def _build_programs(self) -> None:
        cfg, par, params = self.cfg, self.par, self.params

        # the weights are an argument, not a closed-over constant: a
        # constant would be embedded in the executable (GBs at full width)
        def seg(cache, mode, tok, pos, key, rem, pfill, pend, plen, params):
            return mixed_segment(cfg, par, params, cache, mode, tok, pos, key,
                                 rem, pfill, pend, plen, num_steps=self.segment,
                                 prefill_chunk=self.cp,
                                 n_host_chunks=self.n_host_chunks,
                                 sampling=self.sampling, stop_tokens=self._stop,
                                 pad_id=self.pad_id)

        tel = self.telemetry
        sh = self._segment_shardings()
        if sh is None:
            self._cache_sh = None
            self._seg_params = params
            self._segment = jax.jit(per_engine(seg, tel, "segment"))
            self._reset = jax.jit(per_engine(reset_slot, tel, "reset"))
        else:
            in_sh, out_sh = sh
            csh, r = in_sh[0], self.par.ns()
            self._cache_sh = csh
            self._seg_params = jax.device_put(params, r)
            self._segment = jax.jit(per_engine(seg, tel, "segment"),
                                    in_shardings=in_sh,
                                    out_shardings=out_sh)
            self._reset = jax.jit(per_engine(reset_slot, tel, "reset"),
                                  in_shardings=(csh, r), out_shardings=csh)

    # -- helpers ---------------------------------------------------------
    def compiled_programs(self) -> Dict[str, int]:
        """Live compile count per engine program (bounded-set assertion for
        tests: one mixed segment + one reset, no per-bucket/per-length
        specializations within a workload)."""
        return {"segment": self._segment._cache_size(),
                "reset": self._reset._cache_size()}

    def _validate(self, prompts: Sequence[Sequence[int]]) -> None:
        for i, p in enumerate(prompts):
            if len(p) == 0:
                raise ValueError(f"prompt {i} is empty; prompts must have "
                                 f"length > 0 (any length — prompts longer "
                                 f"than bucket={self.bucket} just take more "
                                 f"prefill chunks)")

    def _capacity(self, prompts: Sequence[Sequence[int]]) -> Tuple[int, int]:
        """(P, S): pending-buffer length and cache capacity for a workload —
        the bucket floor or the longest prompt, rounded up to whole prefill
        chunks (and S to whole host-KV slabs when streaming)."""
        longest = max((len(p) for p in prompts), default=1)
        P = -(-max(self.bucket, longest) // self.cp) * self.cp
        S = P + self.max_new
        if self.n_host_chunks:
            S = -(-S // self.n_host_chunks) * self.n_host_chunks
        return P, S

    # -- slot-lifecycle hooks (overridden by the paged engine) -----------
    def _begin(self, B: int, P: int, S: int):
        """Start a workload: return the cache the segments will carry.
        On a mesh, committed to the segment's cache sharding up front —
        every reset/segment call then sees one input signature, keeping
        the compiled-program set at exactly two."""
        cache = SV.init_cache(self.cfg, B, S)
        if self._cache_sh is not None:
            cache = jax.device_put(cache, self._cache_sh)
        return cache

    def _admit(self, cache, s: int, idx: int, prompt, active: bool,
               budget: Optional[int] = None):
        """Claim slot ``s`` for request ``idx``: invalidate the slot's rows
        and return ``(cache, resume)`` where ``resume`` is how many prompt
        tokens are ALREADY cached (prefill starts there; dense: 0).  May
        return ``None`` to defer the request when resources are
        momentarily exhausted — only legal while other slots are still
        ``active`` (they will free resources); otherwise raise.  Any
        device work the admission implies (paged: fresh-page resets, COW
        copies, spill-tier promote scatters) is dispatched here, before
        the slot's first segment sees the cache.  ``budget`` is the
        decode-token reservation (``None`` → ``max_new_tokens``); a
        preemption-resuming scheduler passes the request's REMAINING
        budget so re-admission doesn't over-reserve pages."""
        self.last_stats["resets"] += 1
        return self._reset(cache, s), 0

    def _dispatch(self, cache, mode, tok, pos, key, rem, pfill, pend, plen):
        return self._segment(cache, mode, tok, pos, key, rem, pfill, pend, plen,
                             self._seg_params)

    def _post_dispatch(self, mode, pfill, plen, pend, owner) -> None:
        """Host-side bookkeeping after each segment (paged: radix publish)."""

    def _release(self, s: int) -> None:
        """Slot ``s`` went FREE and its owner was harvested."""

    def _end(self, cache) -> None:
        """Workload drained (every slot released)."""

    # -- the scheduler ---------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]],
                 key: Optional[jnp.ndarray] = None) -> List[List[int]]:
        """Run every prompt to completion (stop token or ``max_new_tokens``),
        re-using slots as sequences finish.  Returns one generated-token
        list per prompt (stop token included when one fired), in order.

        Per-dispatch timing/occupancy lands in ``self.last_stats`` —
        ``steps`` is a list of ``{ms, prefilling, emitted}`` records (one
        per segment dispatch; run with ``segment=1`` for true per-step
        inter-token latencies), plus ``dispatches``/``resets`` counters.
        """
        self._validate(prompts)
        key = jax.random.PRNGKey(0) if key is None else key
        queue = collections.deque(enumerate(prompts))
        out: List[List[int]] = [[] for _ in prompts]
        B = self.slots
        P, S = self._capacity(prompts)
        stats = self.telemetry.stats_view(
            {"steps": self.telemetry.steps_ring(), "dispatches": 0,
             "resets": 0, "capacity": S, "pending_len": P})
        self.last_stats = stats
        cache = self._begin(B, P, S)
        mode = np.full(B, FREE, np.int32)
        tok = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        rem = np.zeros(B, np.int32)
        pfill = np.zeros(B, np.int32)
        pend = np.full((B, P), self.pad_id, np.int32)
        plen = np.ones(B, np.int32)
        owner: List[Optional[int]] = [None] * B

        while True:
            for s in range(B):
                if owner[s] is not None or not queue:
                    continue
                idx, prompt = queue[0]
                active = any(o is not None for o in owner)
                admitted = self._admit(cache, s, idx, prompt, active)
                if admitted is None:  # deferred (pool pressure): retry later
                    break
                cache, resume = admitted
                queue.popleft()
                owner[s] = idx
                n = len(prompt)
                self.telemetry.event(
                    "request.admit", request=idx, slot=s,
                    step=stats["dispatches"], prompt_len=n,
                    prefix_hit=int(resume))
                pend[s, :n] = list(prompt)
                pend[s, n:] = self.pad_id
                plen[s], pfill[s], mode[s] = n, resume, PREFILL
                rem[s], pos[s], tok[s] = self.max_new, 0, self.pad_id
            if all(o is None for o in owner):
                break
            key, sub = jax.random.split(key)
            n_prefilling = int((mode == PREFILL).sum())
            with TM.timed_dispatch(self.telemetry, stats,
                                   prefilling=n_prefilling) as td:
                emits, valids, aux = self._dispatch(
                    cache, mode, tok, pos, sub, rem, pfill, pend, plen)
                cache = aux["cache"]
                mode, tok, pos, rem, pfill, em, va = (
                    np.array(x) for x in jax.device_get(
                        (aux["mode"], aux["tok"], aux["pos"], aux["rem"],
                         aux["pfill"], emits, valids)))
                td.emitted = int(va.sum())
            self._post_dispatch(mode, pfill, plen, pend, owner)
            for s in range(B):
                if owner[s] is None:
                    continue
                got = [int(t) for t, v in zip(em[s], va[s]) if v]
                out[owner[s]].extend(got)
                if got:
                    self.telemetry.event(
                        "request.emit", request=owner[s], slot=s,
                        step=stats["dispatches"], n=len(got))
                if mode[s] == FREE:
                    self._release(s)
                    self.telemetry.event(
                        "request.complete", request=owner[s], slot=s,
                        step=stats["dispatches"], n=len(out[owner[s]]))
                    owner[s] = None
        self._end(cache)
        return out


class BlockingServeEngine:
    """The PR 3 continuous-batching engine, kept as the measured baseline
    the fused scheduler is compared against (``benchmarks/serve_bench.py``).

    Prompts are right-padded into a fixed ``bucket`` length and prefilled
    position-masked (``prefill_step(..., lengths=...)``), decode runs in
    jitted ``decode_tokens`` segments of ``segment`` steps, and between
    segments finished rows are harvested and their slots re-prefilled with
    queued prompts — three compiled programs total (batched prefill,
    single-row refill prefill, decode segment), but every refill STOPS THE
    WORLD: all other slots sit idle for a full-bucket prefill.

    Variable prompt lengths require a pure global-attention layout (see
    ``prefill_step``); recurrent archs can only use this engine when every
    prompt exactly fills the bucket.  ``ServeEngine`` lifts both limits.
    """

    def __init__(self, cfg: ModelConfig, params: Params, *, slots: int,
                 bucket: int, max_new_tokens: int,
                 n_host_chunks: int = 0, sampling: SamplingConfig = GREEDY,
                 stop_tokens: Sequence[int] = (), pad_id: int = 0,
                 segment: int = 8, par: Optional[ParallelContext] = None):
        self.cfg, self.params, self.par = cfg, params, par
        self.slots, self.bucket = slots, bucket
        self.max_new = max_new_tokens
        self.max_len = bucket + max_new_tokens
        self.sampling, self.pad_id = sampling, pad_id
        self.segment = segment
        stop_tokens = tuple(stop_tokens)
        self._stop_set = frozenset(int(t) for t in stop_tokens)
        # three-program engine, and prefill legitimately compiles twice
        # (batched initial fill + single-row refill) — alert past that
        self.telemetry = TM.Telemetry(component="blocking-engine",
                                      program_limit=2)
        self.last_stats: Dict[str, Any] = self.telemetry.stats_view()
        if n_host_chunks and self.max_len % n_host_chunks:
            # models/serve.py silently falls back to on-device attention for
            # non-dividing chunk counts — the operator would be serving a
            # different program than requested
            raise ValueError(
                f"n_host_chunks={n_host_chunks} does not divide the cache "
                f"length bucket+max_new_tokens={self.max_len}; host-KV "
                f"streaming requires equal slabs")

        def prefill(toks, lengths):
            return SV.prefill_step(cfg, par, params, {"tokens": toks},
                                   max_len=self.max_len, lengths=lengths)

        self._prefill = jax.jit(per_engine(prefill, self.telemetry,
                                           "prefill"))

        def decode_seg(cache, tok, pos, key, done, rem):
            return decode_tokens(cfg, par, params, cache, tok, pos,
                                 num_steps=segment, n_host_chunks=n_host_chunks,
                                 sampling=sampling, stop_tokens=stop_tokens,
                                 pad_id=pad_id, key=key, done=done,
                                 remaining=rem)

        self._decode = jax.jit(per_engine(decode_seg, self.telemetry,
                                          "decode"))
        self._insert = jax.jit(per_engine(insert_slot, self.telemetry,
                                          "insert"))

    # -- helpers ---------------------------------------------------------
    def _pad(self, rows: List[List[int]]) -> Tuple[jnp.ndarray, jnp.ndarray]:
        lengths = [len(r) for r in rows]
        for i, n in enumerate(lengths):
            if not 0 < n <= self.bucket:
                raise ValueError(
                    f"prompt {i} has length {n}; the blocking engine "
                    f"requires lengths in (0, bucket={self.bucket}] — use "
                    f"ServeEngine for longer prompts (chunked prefill)")
        toks = jnp.asarray(
            [list(r) + [self.pad_id] * (self.bucket - len(r)) for r in rows],
            jnp.int32)
        return toks, jnp.asarray(lengths, jnp.int32)

    # -- the scheduler ---------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]],
                 key: Optional[jnp.ndarray] = None) -> List[List[int]]:
        """Run every prompt to completion (stop token or ``max_new_tokens``),
        re-using slots as sequences finish.  Returns one generated-token
        list per prompt (stop token included when one fired), in order."""
        key = jax.random.PRNGKey(0) if key is None else key
        queue = collections.deque(enumerate(prompts))
        out: List[List[int]] = [[] for _ in prompts]
        B = self.slots
        stats = self.telemetry.stats_view(
            {"steps": self.telemetry.steps_ring(), "dispatches": 0,
             "refills": 0})
        self.last_stats = stats

        # initial fill: pad the first B prompts into one batched prefill;
        # short queues fill trailing slots with a dummy row that starts done
        first = [queue.popleft() for _ in range(min(B, len(queue)))]
        rows = [list(p) for _, p in first] + [[self.pad_id] * self.bucket] * (B - len(first))
        toks, lengths = self._pad(rows)
        # no pad tokens -> unmasked prefill (lengths=None): this is the path
        # recurrent layouts can take, since prefill_step refuses lengths=...
        no_pads = all(len(r) == self.bucket for r in rows)
        logits, cache = self._prefill(toks, None if no_pads else lengths)
        stats["dispatches"] += 1
        key, sub = jax.random.split(key)
        tok = sample_token(logits[:, : self.cfg.vocab_size], sub, self.sampling)
        owner: List[Optional[int]] = [i for i, _ in first] + [None] * (B - len(first))
        for s, o in enumerate(owner):
            if o is not None:
                out[o].append(int(tok[s]))
        pos = lengths
        # a prefill-sampled first token may itself be a stop token (or the
        # whole budget): such rows start done and are refilled at the next
        # harvest, never entering the scan as live
        done = jnp.asarray([o is None or int(tok[s]) in self._stop_set
                            or self.max_new <= 1
                            for s, o in enumerate(owner)])
        rem = jnp.full((B,), self.max_new - 1, jnp.int32)
        tok = tok[:, None]

        while not all(o is None for o in owner):
            # the span times the whole stop-the-world segment: decode +
            # harvest + any synchronous refill prefills (the stall the
            # fused engine is measured against)
            with TM.timed_dispatch(self.telemetry, stats) as td:
                n_refills = 0
                rem_before = rem
                toks_seg, aux = self._decode(cache, tok, pos, key, done, rem)
                cache, tok, pos, key = aux["cache"], aux["tok"], aux["pos"], aux["key"]
                done, rem = aux["done"], aux["remaining"]
                emitted = jax.device_get(rem_before - rem)
                seg_host = jax.device_get(toks_seg)
                done_host = jax.device_get(done)
                for s in range(B):
                    if owner[s] is None:
                        continue
                    out[owner[s]].extend(int(t) for t in seg_host[s, : emitted[s]])
                    if not done_host[s]:
                        continue
                    if not queue:  # finished, nothing queued: park the slot
                        owner[s] = None
                        continue
                    # slot reuse: single-row position-masked prefill + insert —
                    # synchronous: every other slot stalls for the full prefill
                    idx, prompt = queue.popleft()
                    toks1, len1 = self._pad([list(prompt)])
                    logits1, cache1 = self._prefill(
                        toks1, None if len(prompt) == self.bucket else len1)
                    key, sub = jax.random.split(key)
                    t0tok = sample_token(logits1[:, : self.cfg.vocab_size], sub,
                                         self.sampling)
                    cache = self._insert(cache, cache1, s)
                    n_refills += 1
                    stats["dispatches"] += 2
                    owner[s] = idx
                    out[idx].append(int(t0tok[0]))
                    tok = tok.at[s].set(t0tok)
                    pos = pos.at[s].set(len1[0])
                    done = done.at[s].set(int(t0tok[0]) in self._stop_set
                                          or self.max_new <= 1)
                    rem = rem.at[s].set(self.max_new - 1)
                jax.block_until_ready(tok)
                stats["refills"] += n_refills
                td.prefilling = n_refills
                td.emitted = int(emitted.sum())
        return out
