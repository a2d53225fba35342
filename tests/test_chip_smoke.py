"""chip_smoke.py refuses to report without a TPU or without the repo.

The smoke run itself needs a chip; here only its refusals are checked: no
``{"ok": true, ...}`` line and a non-zero exit code.
"""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script: str, cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    proc = _run(SCRIPT, ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_refuses_without_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run(str(alone), str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no repro package" in proc.stderr
