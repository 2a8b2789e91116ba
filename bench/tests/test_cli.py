"""Without a TPU the command exits non-zero and prints no result."""

import os
import subprocess
import sys

import run


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload",
         "er400-q20.solo", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
