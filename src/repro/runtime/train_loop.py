"""Training runtime: jitted step builder + fault-tolerant loop.

Fault tolerance for 1000+ nodes (DESIGN.md §7):
  * periodic async checkpoints (params, optimizer, data-iterator step);
  * SIGTERM/SIGINT triggers a blocking final checkpoint (preemption-safe);
  * `resume="auto"` restores the newest complete checkpoint, including onto
    a *different* mesh (elastic restart after losing nodes);
  * heartbeat/straggler monitor: per-step wall times are z-scored; a
    persistent outlier raises a StragglerAlert so the launcher can re-mesh
    (simulated multi-host demo in examples/fault_tolerance_demo.py).
"""
from __future__ import annotations

import collections
import dataclasses
import signal
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.core import fpdt
from repro.core.parallel import ParallelContext
from repro.models import transformer as T
from repro.optim import adamw
from repro.optim import compression as comp
from repro.runtime import placement
from repro.runtime import telemetry as TM


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    grad_accum: int = 1
    compress_grads: bool = False  # int8 + error feedback (cross-pod traffic)
    straggler_zscore: float = 4.0
    straggler_patience: int = 3


class StragglerAlert(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# step builder
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, par: Optional[ParallelContext],
                    oc: adamw.OptConfig, tc: Optional[TrainConfig] = None):
    """(params, opt_state, batch[, residuals]) -> (params, opt_state, metrics)."""
    tc = tc or TrainConfig()

    def loss(p, b):
        return T.loss_fn(cfg, par, p, b)

    def step(params, opt_state, batch, residuals=None):
        if tc.grad_accum > 1:
            def micro(i, carry):
                gsum, lsum = carry
                mb = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // tc.grad_accum), x.shape[0] // tc.grad_accum, 0
                    ),
                    batch,
                )
                (l, _), g = jax.value_and_grad(loss, has_aux=True)(params, mb)
                return jax.tree.map(jnp.add, gsum, g), lsum + l

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, lsum = jax.lax.fori_loop(0, tc.grad_accum, micro, (zeros, jnp.float32(0)))
            grads = jax.tree.map(lambda g: g / tc.grad_accum, grads)
            lval = lsum / tc.grad_accum
            metrics = {"loss": lval}
        else:
            (lval, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params, batch)
        if tc.compress_grads and residuals is not None:
            grads, residuals = comp.tree_quantize_with_feedback(grads, residuals)
        params, opt_state, om = adamw.apply(oc, params, grads, opt_state)
        metrics = dict(metrics)
        metrics.update(om)
        out = (params, opt_state, metrics)
        return out + ((residuals,) if residuals is not None else ())

    return step


# ---------------------------------------------------------------------------
# loop with fault tolerance
# ---------------------------------------------------------------------------


class HeartbeatMonitor:
    """Detects persistent stragglers from per-step wall time."""

    HISTORY = 100  # steps a new step time is scored against

    def __init__(self, zscore: float, patience: int):
        self.times: collections.deque = collections.deque(maxlen=self.HISTORY + 1)
        self.z = zscore
        self.patience = patience
        self.bad = 0

    def record(self, dt: float) -> None:
        self.times.append(dt)
        hist = list(self.times)[:-1]
        if len(hist) >= 10:
            mu, sd = float(np.mean(hist)), float(np.std(hist)) + 1e-9
            if (dt - mu) / sd > self.z:
                self.bad += 1
            else:
                self.bad = 0
        if self.bad >= self.patience:
            raise StragglerAlert(
                f"step time {dt:.3f}s is a persistent outlier (mu={np.mean(hist):.3f})"
            )


class TrainLoop:
    def __init__(self, cfg, par, oc, tc, step_fn, data_iter, ckpt_mgr=None):
        self.cfg, self.par, self.oc, self.tc = cfg, par, oc, tc
        self.step_fn = step_fn
        self.data = data_iter
        self.ckpt = ckpt_mgr
        self.monitor = HeartbeatMonitor(tc.straggler_zscore, tc.straggler_patience)
        self._stop = False
        self.history: list = []
        self.telemetry = TM.Telemetry(component="train")

    def _install_signals(self):
        def handler(signum, frame):
            self._stop = True

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass  # not main thread

    def _record_schedule(self, batch):
        """Gauges of one global attention layer on the scan-compiled FPDT
        path: ``fpdt_fwd_kv_fetches``, the KV chunk fetches of one forward
        pass, and of those and of the Fig. 7 q chunk fetches the ones issued
        on the overlapped path (``fpdt_fwd_kv_fetches_overlapped``,
        ``fpdt_bwd_q_fetches_overlapped``; ``fpdt.overlapped_fetches``)."""
        cfg = self.cfg
        u = cfg.fpdt_chunks
        if u > 1 and "attn" in cfg.layer_kinds():
            cq = batch["tokens"].shape[1] // u
            gauge = self.telemetry.registry.gauge
            gauge("fpdt_fwd_kv_fetches").set(
                fpdt.fwd_kv_fetches(u, cq=cq, sparsity=cfg.attn_sparsity))
            par = self.par if self.par is not None else ParallelContext(mesh=None)
            fwd, bwd = fpdt.overlapped_fetches(
                u, cq=cq, sparsity=cfg.attn_sparsity,
                head_groups=fpdt.head_groups(par, T.attn_kind(cfg, self.par), cfg.num_kv_heads),
                offload=cfg.fpdt_offload and par.offload_to_host)
            gauge("fpdt_fwd_kv_fetches_overlapped").set(fwd)
            gauge("fpdt_bwd_q_fetches_overlapped").set(bwd)

    def run(self, params, opt_state, start_step: int = 0, put_batch=None,
            on_step: Optional[Callable[[int, Any, Dict], None]] = None):
        """Train to ``tc.steps``; ``on_step(step, params, metrics)`` runs
        after each step's result is ready.

        Each step is a ``jax.profiler.StepTraceAnnotation("train")`` holding
        ``train.feed``, ``train.dispatch`` and ``train.block`` spans, so a
        profiler trace puts the host's phases beside the device's ops.  A
        jitted ``step_fn`` that compiles a new program counts it through
        ``Telemetry.compile_event("train_step")``; the first batch sets the
        FPDT schedule's gauges (``_record_schedule``)."""
        self._install_signals()
        if put_batch is None:
            # default batch staging routes through the placement layer
            pol = self.par.pol if self.par is not None else placement.default_policy()
            put_batch = lambda b: {k: pol.put(jnp.asarray(v)) for k, v in b.items()}  # noqa: E731
        # a jit's count of compiled programs; an AOT-compiled step has none
        programs = getattr(self.step_fn, "_cache_size", lambda: 0)
        step = start_step
        self.data.restore(start_step)
        while step < self.tc.steps and not self._stop:
            t0 = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("train", step_num=step + 1):
                with jax.profiler.TraceAnnotation("train.feed"):
                    batch = put_batch(next(self.data))
                if step == start_step:
                    self._record_schedule(batch)
                n_programs = programs()
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    params, opt_state, metrics = self.step_fn(params, opt_state, batch)[:3]
                with jax.profiler.TraceAnnotation("train.block"):
                    jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            step += 1
            for _ in range(programs() - n_programs):
                self.telemetry.compile_event("train_step", step=step)
            self.history.append({"step": step, "loss": float(metrics["loss"]), "dt": dt})
            self.telemetry.registry.histogram("train_step_ms").observe(dt * 1e3)
            self.telemetry.registry.counter("train_steps").inc()
            self.telemetry.registry.gauge("train_loss").set(float(metrics["loss"]))
            self.telemetry.event("train.step", step=step, dur_ms=dt * 1e3,
                                 loss=float(metrics["loss"]))
            if on_step is not None:
                on_step(step, params, metrics)
            if step % self.tc.log_every == 0:
                print(f"step {step:6d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} {dt*1000:.0f}ms")
            if self.ckpt and step % self.tc.ckpt_every == 0:
                self.ckpt.save(step, {"params": params, "opt": opt_state},
                               extra={"data_step": self.data.state()})
            try:
                self.monitor.record(dt)
            except StragglerAlert as e:
                print(f"[ft] straggler detected: {e}; requesting re-mesh")
                break
        if self.ckpt and (self._stop or step >= self.tc.steps):
            # preemption or completion: blocking final save
            self.ckpt.save(step, {"params": params, "opt": opt_state},
                           extra={"data_step": self.data.state()}, blocking=True)
        return params, opt_state, step
