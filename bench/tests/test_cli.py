"""The command refuses to run, and prints no result, without a TPU."""
import os
import subprocess
import sys

from conftest import BENCH


def test_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "acoustic-so4-512.propagate", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(BENCH))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
