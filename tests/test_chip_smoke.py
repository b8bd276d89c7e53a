"""chip_smoke.py measures the chip or nothing: without a TPU, and without
the rest of the repo beside it, it exits nonzero and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-repo", "script-alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    script = SCRIPT
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    if not alone:
        assert "not a TPU" in r.stderr, r.stderr[-2000:]
