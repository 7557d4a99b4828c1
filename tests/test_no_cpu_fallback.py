"""The chip entry points have no CPU path: without a TPU they exit
non-zero and print no result (no CPU number, no cached chip number)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_chip_means_no_result(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, script], cwd=REPO, env=env,
                       stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.decode().strip() == "", p.stdout.decode()[-500:]
    assert b"no TPU" in p.stderr
