"""The harness's own tests.  Not part of tier-1 (tests/): run by hand,

    python -m pytest bench/tests -q -p no:cacheprovider

on the CPU at tiny sizes.  They never touch a chip."""
import os
import sys

# the CPU, as four devices, before anything imports JAX
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("CYLON_TEST_NO_COMPILE_CACHE", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
