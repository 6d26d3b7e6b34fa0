"""Test harness: simulate an 8-device TPU mesh on host CPU.

The reference tests every distributed op at world sizes 1/2/4 via
``mpirun --oversubscribe -np N`` (reference: cpp/test/CMakeLists.txt:19-50);
the JAX equivalent is a virtual multi-device CPU platform, so the same
shard_map programs that run on a TPU pod execute here on 8 host devices.

Must run before anything imports jax: the platform and the device count
are read when the backend initializes.
"""
import contextlib
import gc
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu"
assert len(jax.devices()) == 8, jax.devices()

# Persistent compile cache: the full tree compiles many hundreds of XLA
# programs in one process, which dominates suite wall time; a warm cache
# removes almost all in-process compilation on repeat runs.  Threshold 0:
# even millisecond compiles are worth caching here.
from cylon_tpu.utils.compile_cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache(min_compile_secs=0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: large-scale property tests (~1M rows/shard)")


@pytest.fixture(scope="session", autouse=True)
def _lock_record_session():
    """CYLON_TPU_LOCK_RECORD=1: wrap the whole test session in the
    cylint Level-3 lock recorder — every in-process lock created by the
    elastic/serve/router suites records its ordering, and a held->
    acquired edge missing from the committed lock-order golden fails the
    session (CY204) the same way the --lockgraph smoke would."""
    from cylon_tpu.analysis import locks

    if not locks.record_enabled():
        yield
        return
    rec = locks.LockRecorder()
    with locks.record_locks(rec):
        yield
    found = locks.check_lockgraph(rec.observed())
    assert not found, "\n".join(f.render() for f in found)


@pytest.fixture(scope="session", autouse=True)
def _trace_dir_isolation(tmp_path_factory):
    """Point CYLON_TPU_TRACE_DIR at a session tmp dir unless the caller
    set one: the flight recorder (obs.fleet) auto-dumps on classified
    terminal events — which fault-injection tests fire constantly — and
    those dumps must not accumulate under the repo's default ./traces."""
    if not os.environ.get("CYLON_TPU_TRACE_DIR"):
        os.environ["CYLON_TPU_TRACE_DIR"] = str(
            tmp_path_factory.mktemp("obs_traces"))


@pytest.fixture(scope="session")
def local_ctx():
    from cylon_tpu.context import CylonContext

    return CylonContext.Init()


def _dist_ctx(world):
    from cylon_tpu.context import CylonContext, TPUConfig

    return CylonContext.InitDistributed(TPUConfig(world_size=world))


@pytest.fixture(scope="session")
def ctx2():
    return _dist_ctx(2)


@pytest.fixture(scope="session")
def ctx4():
    return _dist_ctx(4)


@pytest.fixture(scope="session")
def ctx8():
    return _dist_ctx(8)


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def _drop_traced_programs():
    """Forget every traced program: JAX's caches and the package's jit-plan
    caches (a context's ``_plan_cache`` and ``_shard_fn_cache``, the stream
    kernels).  Their keys do not hold how the local kernels are realized:
    in production that follows the platform and cannot change."""
    from cylon_tpu.context import CylonContext
    from cylon_tpu.stream import incremental

    jax.clear_caches()
    incremental._KERNELS.clear()
    for obj in gc.get_objects():
        if isinstance(obj, CylonContext):
            for name in ("_plan_cache", "_shard_fn_cache"):
                getattr(obj, name, {}).clear()


@contextlib.contextmanager
def _realize(row):
    """Run the body under ``row`` (an ``ops.realization.Realization``) in
    place of the platform's own: the one door by which a test runs the
    other platform's kernels.  Asserts from a jaxpr that the substitution
    took, so that an agreement test never compares a path with itself."""
    from unittest import mock

    import jax.numpy as jnp

    from cylon_tpu.ops import compact, realization

    other = {"sort": "scatter", "scatter": "sort"}[row.permute]
    # the platform's own row substitutes nothing: what is traced stays valid
    substituted = row != realization.current()
    if substituted:
        _drop_traced_programs()
    try:
        with mock.patch.object(realization, "current", return_value=row):
            # a new function: make_jaxpr caches a trace by function
            jaxpr = str(jax.make_jaxpr(
                lambda m: compact.compact_indices(m))(jnp.zeros((8,), bool)))
            assert f" {row.permute}[" in jaxpr, jaxpr
            assert f" {other}[" not in jaxpr, jaxpr
            yield row
    finally:
        if substituted:
            _drop_traced_programs()


@pytest.fixture()
def realize():
    """``with realize(row): ...`` — see ``_realize``."""
    return _realize
