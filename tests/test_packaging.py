"""Wheel packaging (reference: python/setup.py:51-55 builds pycylon
against libcylon; here setup.py's build_py hook compiles and ships the
native .so + C ABI header as package data)."""
import subprocess
import sys
import zipfile
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_wheel_contains_native_artifacts(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-build-isolation",
         "--no-deps", "-w", str(tmp_path), str(REPO)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    wheels = list(tmp_path.glob("cylon_tpu-*.whl"))
    assert len(wheels) == 1, wheels
    names = zipfile.ZipFile(wheels[0]).namelist()
    assert "cylon_tpu/__init__.py" in names
    assert "cylon_tpu/native/libcylon_tpu.so" in names
    assert "cylon_tpu/native/include/cylon_tpu_c.h" in names
    assert any(n.startswith("cylon_tpu/native/src/") and n.endswith(".cpp")
               for n in names)
    assert not any(n.startswith("tests/") for n in names)
