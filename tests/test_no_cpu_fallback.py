"""The measurement entry points fail without a TPU: no CPU pass, no CPU
result (chip_smoke.py's contract; bench.py since the probe-and-fallback
code went)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_entry_point_fails_off_tpu(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=55)
    assert p.returncode != 0, p.stdout[-500:]
    assert "tpu" in p.stderr.lower(), p.stderr[-500:]
    # nothing that a reader of the last line could take for a result
    assert not [ln for ln in p.stdout.splitlines()
                if ln.startswith("{")], p.stdout[-500:]
