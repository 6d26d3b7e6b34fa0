"""The driver's multichip dryrun must stay green at the v5e-16 shape with
the full op set (join sort+hash, two-phase + pipeline groupby, VAR/STDDEV,
NUNIQUE, set ops, task shuffle, range sort incl. strings, HashPartition).

Runs in a SUBPROCESS: xla_force_host_platform_device_count is read at
backend init, and the suite's conftest already pinned this process to 8.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_dryrun_multichip_16_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # dryrun sets its own device count
    proc = subprocess.run(
        [sys.executable, "-c",
         "from __graft_entry__ import dryrun_multichip; "
         "dryrun_multichip(16); print('ok16')"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, timeout=900)
    out = proc.stdout.decode()
    assert proc.returncode == 0, out[-3000:]
    assert "ok16" in out
