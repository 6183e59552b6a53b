"""`chip_smoke.py` refuses to report a result without a TPU, and
without the rest of the repo: non-zero exit and no JSON line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu_or_repo(tmp_path, alone):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if alone:
        shutil.copy(REPO / "chip_smoke.py", tmp_path)
        env.pop("PYTHONPATH", None)
        cwd = tmp_path
    else:
        cwd = REPO
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert not any(line.startswith("{") for line in res.stdout.splitlines())
