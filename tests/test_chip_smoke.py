"""chip_smoke.py refuses to run, and prints no result, where it would not
exercise Mosaic kernels on a TPU: on the CPU backend, and outside a
checkout of the repo."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _run(script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _ok_lines(stdout: str) -> list:
    found = []
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "ok" in rec and "phase" not in rec:
            found.append(rec)
    return found


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(where, tmp_path):
    script = SCRIPT
    if where == "alone":
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
    proc = _run(script)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert _ok_lines(proc.stdout) == []
    assert "chip_smoke:" in proc.stderr
