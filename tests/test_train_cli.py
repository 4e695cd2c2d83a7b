"""The train CLI's body (``repro.launch.train.run``) in-process and on a mesh."""
import math
import os
import subprocess
import sys

import pytest

from repro.launch.train import build_parser, mesh_shape, run

ROOT = os.path.join(os.path.dirname(__file__), "..")
TINY = ["--arch", "llama3.2-1b", "--reduced", "--batch", "2", "--seq", "64",
        "--steps", "2", "--log-every", "1"]


def test_run_in_process_with_depth_cut_and_offload(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))  # leave jax's config alone
    seen = []
    tr = run(build_parser().parse_args(
        TINY + ["--chunks", "4", "--offload", "--num-layers", "1",
                "--opt-state-dtype", "bfloat16"]),
        on_step=lambda step, params, metrics: seen.append((step, float(metrics["loss"]))))
    assert tr.cfg.num_layers == 1 and tr.cfg.fpdt_chunks == 4 and tr.cfg.fpdt_offload
    assert [s for s, _ in seen] == [1, 2]
    assert [h["loss"] for h in tr.loop.history] == [v for _, v in seen]
    assert all(math.isfinite(v) for _, v in seen)
    assert tr.compile_s > 0


@pytest.mark.parametrize("spec,want", [("none", None), ("1x4", (1, 4)), ("2x4", (2, 4))])
def test_mesh_shape(spec, want):
    assert mesh_shape(spec) == want


def test_mesh_shape_refuses_malformed():
    with pytest.raises(SystemExit, match="AxB"):
        mesh_shape("host8")


def test_mesh_run_on_fake_cpu_devices(tmp_path):
    """``--mesh 1x2`` forces two host devices on CPU and trains over them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *TINY, "--mesh", "1x2",
         "--chunks", "2", "--offload"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "mesh=1x2" in r.stdout
    losses = [float(l.split()[3]) for l in r.stdout.splitlines() if l.startswith("step")]
    assert len(losses) == 2 and all(math.isfinite(v) for v in losses)
