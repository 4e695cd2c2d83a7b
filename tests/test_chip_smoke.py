"""chip_smoke.py refuses to run anywhere but on a TPU from a checkout."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":  # a directory holding the script and nothing else
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "FAIL" in r.stderr
