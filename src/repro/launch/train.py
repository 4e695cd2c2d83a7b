"""Training CLI.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 100 --batch 8 --seq 256 [--chunks 4 --offload] [--resume auto]

``--mesh AxB`` trains over an (A data, B model) mesh of the visible
devices: Ulysses/CP sequence parallelism over ``model``, ZeRO sharding over
``data``.  On CPU the device count is forced to A*B (fake host devices);
on an accelerator the mesh takes the first A*B real chips.
``--num-layers`` cuts depth (widths stay the config's own).

``run(args)`` is the whole CLI body, callable in-process with parsed args
(``chip_smoke.py`` drives the chip run through it).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from typing import Any, Callable, Optional


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="depth override (published widths kept)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--chunks", type=int, default=None, help="FPDT u")
    ap.add_argument("--offload", action="store_true")
    ap.add_argument("--remat", default=None, choices=[None, "none", "full", "offload"])
    ap.add_argument("--opt-state-dtype", default=None, choices=[None, "float32", "bfloat16"],
                    help="Adam moment dtype (default: the config's)")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--mesh", default="none",
                    help="'none' or AxB: an (A data, B model) device mesh")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default=None, help="'auto' or a step number")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--trace-out", default="",
                    help="write per-step train.step spans as Chrome "
                         "trace-event JSON (Perfetto-viewable)")
    ap.add_argument("--metrics-out", default="",
                    help="write the step-timing metrics registry as "
                         "Prometheus text exposition")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def mesh_shape(spec: str) -> Optional[tuple]:
    """``'none'`` -> None; ``'AxB'`` -> (A, B)."""
    if spec == "none":
        return None
    try:
        data, model = (int(x) for x in spec.split("x"))
    except ValueError:
        raise SystemExit(f"--mesh must be 'none' or AxB, got {spec!r}") from None
    return data, model


@dataclasses.dataclass
class TrainRun:
    """What one ``run`` built and did (read by ``chip_smoke.py``)."""
    cfg: Any
    loop: Any
    compiled: Any  # the AOT-compiled train step the loop ran
    compile_s: float
    params: Any
    opt_state: Any


def run(args: argparse.Namespace,
        on_step: Optional[Callable[[int, Any, dict], None]] = None) -> TrainRun:
    shape = mesh_shape(args.mesh)
    if shape is not None and "jax" not in sys.modules:
        # fake host devices only matter (and only apply) on the CPU backend
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{shape[0] * shape[1]}").strip()

    import jax
    import jax.numpy as jnp

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import ShapeConfig, get_config, reduced
    from repro.core.parallel import ParallelContext
    from repro.data.pipeline import CheckpointableIterator, make_batch_fn
    from repro.launch import shardings as SH
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.runtime.placement import default_policy
    from repro.runtime.train_loop import TrainConfig, TrainLoop, make_train_step

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    over = {}
    if args.num_layers:
        over["num_layers"] = args.num_layers
    if args.chunks:
        over.update(fpdt_chunks=args.chunks, mlp_chunks=2 * args.chunks)
    if args.offload:
        over["fpdt_offload"] = True
    if args.remat:
        over["remat"] = args.remat
    if args.opt_state_dtype:
        over["opt_state_dtype"] = args.opt_state_dtype
    if over:
        cfg = dataclasses.replace(cfg, **over)

    pol = default_policy()  # probe the backend's memory kinds once
    par = mesh = None
    if shape is not None:
        from repro.launch.mesh import make_compat_mesh

        n = shape[0] * shape[1]
        if len(jax.devices()) < n:
            raise SystemExit(f"--mesh {args.mesh} needs {n} devices, "
                             f"{len(jax.devices())} visible")
        mesh = make_compat_mesh(shape, ("data", "model"), jax.devices()[:n])
        par = ParallelContext.for_mesh(mesh, attn_impl="pallas", placement=pol)

    oc = adamw.OptConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                         total_steps=args.steps, state_dtype=cfg.opt_state_dtype)
    tc = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                     log_every=args.log_every, grad_accum=args.grad_accum,
                     compress_grads=args.compress_grads)
    key = jax.random.PRNGKey(args.seed)
    bf = make_batch_fn(cfg, ShapeConfig("cli", args.seq, args.batch, "train"))
    if mesh is None:
        params = jax.jit(lambda k: T.init_params(cfg, k))(key)
        opt_state = jax.jit(lambda p: adamw.init(oc, p))(params)
        step_jit = jax.jit(make_train_step(cfg, par, oc, tc), donate_argnums=(0, 1))

        def put(b):
            return {k: pol.put(jnp.asarray(v)) for k, v in b.items()}
    else:
        # shard at creation: parameters, moments and batches never gather
        # onto one device
        pshape = jax.eval_shape(lambda k: T.init_params(cfg, k), key)
        psh = SH.param_shardings(cfg, par, pshape)
        osh = SH.opt_shardings(cfg, par, jax.eval_shape(
            lambda p: adamw.init(oc, p), pshape), pshape)
        bsh = SH.batch_shardings(cfg, par, jax.eval_shape(lambda: bf(0)))
        params = jax.jit(lambda k: T.init_params(cfg, k), out_shardings=psh)(key)
        opt_state = jax.jit(lambda p: adamw.init(oc, p), out_shardings=osh)(params)
        # no out_shardings: with offload in the program, jit would wrap each
        # output in a placement annotation, which the SPMD partitioner
        # refuses for partially replicated leaves
        step_jit = jax.jit(make_train_step(cfg, par, oc, tc), donate_argnums=(0, 1),
                           in_shardings=(psh, osh, bsh))

        def put(b):
            return {k: pol.put(jnp.asarray(v), bsh[k]) for k, v in b.items()}

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr and args.resume:
        step = mgr.latest_step() if args.resume == "auto" else int(args.resume)
        if step is not None:
            (restored, extra) = mgr.restore(step, {"params": params, "opt": opt_state})
            params, opt_state = restored["params"], restored["opt"]
            start = step
            print(f"[resume] restored step {step}")

    # compile ahead of the loop so set-up time is reported on its own
    ctx = mesh if mesh is not None else _null()
    with ctx:
        t0 = time.perf_counter()
        compiled = step_jit.lower(params, opt_state, put(bf(start))).compile()
        compile_s = time.perf_counter() - t0
        print(f"[train] {cfg.name}: {cfg.num_layers} layers, seq {args.seq}, "
              f"u={cfg.fpdt_chunks}, offload={cfg.fpdt_offload}, "
              f"mesh={args.mesh}; step compiled in {compile_s:.1f}s")
        loop = TrainLoop(cfg, par, oc, tc, compiled, CheckpointableIterator(bf), mgr)
        loop.telemetry.compile_event("train_step")  # the ahead-of-time compile
        params, opt_state, _ = loop.run(params, opt_state, start_step=start,
                                           put_batch=put, on_step=on_step)
    if args.trace_out or args.metrics_out:
        from repro.runtime import telemetry as TM

        if args.trace_out:
            doc = TM.write_chrome_trace(args.trace_out, loop.telemetry)
            print(f"[telemetry] wrote {len(doc['traceEvents'])} trace "
                  f"events to {args.trace_out}")
        if args.metrics_out:
            TM.write_prometheus(args.metrics_out, loop.telemetry)
            print(f"[telemetry] wrote metrics registry to {args.metrics_out}")
        h = loop.telemetry.registry.histogram("train_step_ms").summary()
        print(f"[telemetry] train_step_ms: p50 {h['p50']:.1f} "
              f"p95 {h['p95']:.1f} mean {h['mean']:.1f} over "
              f"{h['count']} steps")
    return TrainRun(cfg=cfg, loop=loop, compiled=compiled, compile_s=compile_s,
                    params=params, opt_state=opt_state)


def main(argv=None) -> None:
    run(build_parser().parse_args(argv))


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
