"""Backend-aware memory placement: the single audited layer for every
device/host residency decision in the codebase.

The paper's memory headroom comes from offloading idle KV/query chunks to
host memory and double-buffering the fetch so it hides behind chunk compute
(FPDT Fig. 6-7).  Whether that is *possible* — and which memory-kind strings
name the two pools — is a backend property:

  backend        | memory kinds advertised                        | offload
  ---------------|------------------------------------------------|----------
  TPU            | ``device``, ``pinned_host``, ``unpinned_host`` | supported
  GPU            | ``device``, ``pinned_host``                    | supported
  CPU (jax 0.9)  | ``device``, ``pinned_host``, ``unpinned_host`` | supported

``PlacementPolicy`` probes the backend once (``device.addressable_memories()``
/ ``device.default_memory()``) and records the compute and offload memory
kinds.  Offload requested on a backend with no distinct host pool is an
error, except on CPU, where ``to_host``/``to_device`` become identity
functions and a warning is logged once (a CPU build without the pool still
runs every test).  An accelerator run never trains with offload silently
off.

No op may mix memory kinds (jax refuses it): data moves between the pools
only through ``to_host``/``to_device``, and stores kept in the host pool are
written and read host-side (see ``core/fpdt.py``).

All ``jax.device_put`` / ``memory_kind`` decisions route through this
module (enforced: ``grep -rn 'memory_kind=' src/ | grep -v placement``
must return nothing).  See ``docs/placement.md`` for the support matrix.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

# The memory-kind names backends advertise for the two pools the FPDT
# schedule cares about (compute-resident vs. offloaded-idle).
HOST_MEMORY_KIND = "pinned_host"

_WARNED: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        log.warning(msg)


@dataclasses.dataclass(frozen=True)
class PlacementPolicy:
    """Immutable record of one backend's memory capabilities.

    Frozen + hashable on purpose: it rides inside ``ParallelContext``,
    which keys the per-config ``lru_cache`` of compiled FPDT pipelines.

    ``device_kind``   — the backend's default (compute) memory kind.
    ``host_kind``     — the offload pool, or ``None`` when the backend has
                        no host pool distinct from its default memory.
    ``offload_enabled`` — operator switch; ``False`` forces no-op placement
                        even on capable backends (the dry-run uses this).
    ``device``        — the probed device, target of mesh-less placement
                        (``None``: the first visible device).
    """

    device_kind: Optional[str] = None
    host_kind: Optional[str] = None
    backend: str = "unknown"
    offload_enabled: bool = True
    device: Optional[Any] = None

    # -- capability probe ------------------------------------------------
    @classmethod
    def probe(cls, device: Optional[Any] = None, *,
              offload_enabled: bool = True) -> "PlacementPolicy":
        """Inspect one device's memory spaces (once; result is immutable)."""
        if device is None:
            device = jax.devices()[0]
        kinds = {m.kind for m in device.addressable_memories()}
        default = device.default_memory().kind
        host = HOST_MEMORY_KIND if HOST_MEMORY_KIND in kinds else None
        if host is not None and host == default:
            # a "host" pool that IS the default memory is not an offload
            # target (there is nowhere to offload *from*)
            host = None
        return cls(device_kind=default, host_kind=host,
                   backend=getattr(device, "platform", "unknown"),
                   offload_enabled=offload_enabled, device=device)

    # -- capabilities ----------------------------------------------------
    @property
    def supports_pinned_host(self) -> bool:
        """Backend advertises a pinned-host pool distinct from compute memory."""
        return self.host_kind is not None

    @property
    def can_offload(self) -> bool:
        """Offload is both possible (backend) and enabled (operator)."""
        return self.offload_enabled and self.supports_pinned_host

    def _noop(self, verb: str):
        if self.offload_enabled and self.backend != "cpu":
            raise RuntimeError(
                f"[placement] {verb} requested but backend '{self.backend}' "
                f"has no '{HOST_MEMORY_KIND}' memory distinct from its "
                f"default ('{self.device_kind}')")
        _warn_once(
            f"{self.backend}:{verb}",
            f"[placement] {verb} requested but backend '{self.backend}' has "
            f"no '{HOST_MEMORY_KIND}' memory distinct from its default "
            f"('{self.device_kind}'); offload degrades to a no-op.",
        )

    # -- sharding construction ------------------------------------------
    def ns(self, mesh: Optional[Mesh], *spec, on_host: bool = False
           ) -> Optional[NamedSharding]:
        """NamedSharding over ``mesh`` with the policy's memory kind.

        ``on_host=True`` targets the offload pool when supported and
        silently falls back to a plain (default-memory) sharding when not —
        callers never name a memory kind themselves.
        """
        if mesh is None:
            return None
        kw = {}
        if self.can_offload:
            kw["memory_kind"] = self.host_kind if on_host else self.device_kind
        return NamedSharding(mesh, P(*spec), **kw)

    def host_sharding(self, mesh: Optional[Mesh], *spec) -> Optional[NamedSharding]:
        return self.ns(mesh, *spec, on_host=True)

    def device_sharding(self, mesh: Optional[Mesh], *spec) -> Optional[NamedSharding]:
        return self.ns(mesh, *spec, on_host=False)

    def _single(self, on_host: bool):
        kind = self.host_kind if on_host else self.device_kind
        device = self.device if self.device is not None else jax.devices()[0]
        return jax.sharding.SingleDeviceSharding(
            device, **({"memory_kind": kind} if kind else {}))

    # -- placement ops ---------------------------------------------------
    def to_host(self, x, mesh: Optional[Mesh] = None,
                spec: Sequence = ()):  # noqa: ANN001 - jax array/tracer
        """Move ``x`` to the offload pool; identity on incapable backends."""
        if not self.can_offload:
            self._noop("to_host")
            return x
        s = self._single(True) if mesh is None else self.host_sharding(mesh, *spec)
        return jax.device_put(x, s)

    def to_device(self, x, mesh: Optional[Mesh] = None, spec: Sequence = ()):
        """Fetch ``x`` back into compute memory; identity when no offload."""
        if not self.can_offload:
            self._noop("to_device")
            return x
        s = self._single(False) if mesh is None else self.device_sharding(mesh, *spec)
        return jax.device_put(x, s)

    def put(self, x, sharding=None):
        """Audited passthrough for plain (default-memory) ``device_put`` —
        checkpoint restore, batch staging.  Never names a memory kind."""
        return jax.device_put(x, sharding) if sharding is not None else jax.device_put(x)

    # -- remat offload ---------------------------------------------------
    def remat_policy(self, offload_names: Sequence[str] = ("block_in",)):
        """Checkpoint policy for ``remat='offload'``: offload the named
        residuals to the host pool, falling back to full remat (save
        nothing) when the backend can't host-offload."""
        if not self.can_offload:
            self._noop("remat-offload")
            return jax.checkpoint_policies.nothing_saveable
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(offload_names),
            offload_src=self.device_kind or "device",
            offload_dst=self.host_kind,
        )


@functools.lru_cache(maxsize=None)
def default_policy(offload_enabled: bool = True) -> PlacementPolicy:
    """The process-wide policy for the default backend (probed once)."""
    return PlacementPolicy.probe(offload_enabled=offload_enabled)


# ---------------------------------------------------------------------------
# explicit double buffering
# ---------------------------------------------------------------------------


def fori_double_buffered(lo, hi, fetch: Callable, body: Callable, init,
                         *, live: Optional[Callable] = None):
    """Two-deep prefetch pipeline over a traced chunk loop (FPDT Fig. 6).

    Runs ``carry = body(idx, fetch(idx), carry)`` for ``idx`` in ``[lo, hi)``
    — ``lo``/``hi`` may be traced (lowers to a while loop).  The fetched
    value consumed at iteration ``idx`` is carried in the loop state and the
    *next* consumed chunk's fetch is issued *before* ``body(idx)``'s kernels
    in program order, so on offload-capable backends the host->device copy
    of the next chunk can overlap the current chunk's compute.  Whether it
    does is the compiler's schedule: XLA's TPU scheduler overlaps a host
    transfer with a loop but not with a Mosaic kernel in the same
    computation, so a ``body`` whose work is Pallas kernels runs them inside
    a loop of its own (``core/fpdt.py``).

    Carry contract (everything rides a ``fori_loop`` state, so all of it
    must be shape/dtype-stable across iterations):
      * the loop state is ``(prefetch_buffer, carry)``; ``fetch(idx)`` must
        return the same pytree structure/shapes/dtypes for every ``idx``
        (it is probed once via ``jax.eval_shape`` on the live path);
      * ``init`` must match the structure ``body`` returns — ``body`` may
        not change the carry's shape;
      * ``fetch``/``body``/``live`` must be pure; ``fetch`` runs under
        ``lax.cond`` on the live path, so its placement ops must be legal
        in traced context (``device_put`` with memory-kind shardings is).
    Returns the final user carry.

    Without ``live`` every index is consumed, and ``fetch`` runs exactly
    ``hi - lo`` times, with no ``lax.cond`` inside the loop: the loop runs
    ``[lo, hi - 1)`` and the last index after it, so no tail prefetch is
    issued.  An empty range returns ``init`` without a fetch (a traced
    range through one ``lax.cond`` around the whole).

    ``live(idx) -> bool tracer`` optionally restricts the schedule to live
    indices: dead (window/sparsity-skipped) iterations are complete no-ops
    — no fetch, no body — and each live iteration prefetches the next
    *live* index (a traced search), so
    sparse schedules keep the copy/compute overlap instead of issuing
    fetches from skipped iterations that overlap nothing.  Here fetches
    run under ``lax.cond`` and the tail iteration's clamped prefetch is
    never consumed.
    """
    static = isinstance(lo, int) and isinstance(hi, int)
    if live is None and static and lo >= hi:
        return init
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)

    def clamp(idx):
        return jnp.clip(idx, 0, jnp.maximum(hi - 1, 0))

    if live is None:
        def step(idx, state):
            buf, carry = state
            nxt = fetch(idx + 1)
            return nxt, body(idx, buf, carry)

        def run():
            # clamp(lo) is lo on a non-empty range; a traced index keeps the
            # first fetch's op_name in the compiled program
            buf, carry = jax.lax.fori_loop(lo, hi - 1, step, (fetch(clamp(lo)), init))
            return body(hi - 1, buf, carry)
        return run() if static else jax.lax.cond(hi > lo, run, lambda: init)

    def next_live(idx):
        """Smallest live index in (idx, hi); hi when none (live() must be
        pure index arithmetic — it is probed past the range)."""
        return jax.lax.while_loop(
            lambda t: (t < hi) & ~live(t), lambda t: t + 1, idx + 1)

    def zeros_like_fetch():
        shapes = jax.eval_shape(fetch, clamp(lo))
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    first = next_live(lo - 1)
    buf0 = jax.lax.cond(first < hi, lambda: fetch(clamp(first)), zeros_like_fetch)

    def step(idx, state):
        buf, carry = state

        def live_step():
            nxt = next_live(idx)
            nbuf = jax.lax.cond(nxt < hi, lambda: fetch(clamp(nxt)), lambda: buf)
            return nbuf, body(idx, buf, carry)

        return jax.lax.cond(live(idx), live_step, lambda: (buf, carry))

    _, carry = jax.lax.fori_loop(lo, hi, step, (buf0, init))
    return carry
