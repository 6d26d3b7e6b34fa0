"""No CPU mode: on anything but a TPU the command exits non-zero and
prints no result line."""
import os
import subprocess
import sys

from bench.tests.conftest import ROOT


def test_cpu_run_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CYLON_TEST_NO_COMPILE_CACHE="1")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "join_gbs.uniform.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not 'tpu'" in proc.stderr
