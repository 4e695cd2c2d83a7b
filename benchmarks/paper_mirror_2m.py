import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Paper-mirror cell: llama-8b at 2M tokens (batch 1), FPDT u=32 — the
configuration class of the paper's headline claim (8B @ 2M).  Lowers and
compiles train_step on the single-pod production mesh; records
memory/cost/collectives like the dry-run.

  PYTHONPATH=src python -m benchmarks.paper_mirror_2m
"""
import json
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import ShapeConfig, get_config
from repro.core.parallel import ParallelContext
from repro.launch import steps as ST
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.dryrun import parse_collectives
from repro.launch.mesh import dp_axes_of, make_production_mesh


def main():
    setup_compile_cache()
    shape = ShapeConfig("train_2m", 2_097_152, 1, "train")
    mesh = make_production_mesh(multi_pod=False)
    par = ParallelContext(mesh=mesh, dp_axes=dp_axes_of(mesh),
                          attn_impl="xla_flash", offload_to_host=False)
    cfg = ST.tuned_config(get_config("llama-8b"), shape)  # u = 32 (64K chunks)
    print(f"llama-8b @ 2M tokens, FPDT u={cfg.fpdt_chunks}, "
          f"mlp_chunks={cfg.mlp_chunks}, remat={cfg.remat}")
    fn, args, in_sh, out_sh, donate = ST.build(cfg, par, shape)
    with mesh:
        compiled = (jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                            donate_argnums=donate).lower(*args).compile())
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    rec = {
        "cell": "llama-8b_train_2m_single", "chunks": cfg.fpdt_chunks,
        "temp_gib": ma.temp_size_in_bytes / 2**30,
        "args_gib": ma.argument_size_in_bytes / 2**30,
        "flops_text": float(ca.get("flops", 0)),
        "collectives": parse_collectives(compiled.as_text()),
    }
    os.makedirs("experiments/paper_mirror", exist_ok=True)
    with open("experiments/paper_mirror/llama8b_2m.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(f"COMPILED: temp={rec['temp_gib']:.2f} GiB/device, "
          f"args={rec['args_gib']:.2f} GiB/device")
    print({k: v["count"] for k, v in rec["collectives"].items()})


if __name__ == "__main__":
    main()
