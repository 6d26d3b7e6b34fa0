"""chip_smoke.py's phases on the CPU mesh at a tiny size, its platform
check, and the two helpers it leans on (compile cache, ragged probe).

The script itself must FAIL here — it has no "CPU ok" mode — so the phases
are rehearsed by calling its functions, the way the sandbox rehearsal does.
"""
import os
import subprocess
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

ROWS, SEED = 4096, 12345


@pytest.fixture(scope="module")
def world1():
    from cylon_tpu import CylonContext, TPUConfig

    return CylonContext.InitDistributed(TPUConfig(world_size=1))


@pytest.fixture(scope="module")
def data():
    return chip_smoke.make_data(ROWS, SEED)


@pytest.fixture(scope="module")
def main_phase(world1, data):
    return chip_smoke.phase_main(world1, data)


def test_kernels_phase_agrees_with_numpy_and_pandas():
    rec = chip_smoke.phase_kernels(ROWS, SEED, interpret=True)
    assert set(rec["first_second_s"]) >= {
        "sort_join", "hash_join", "groupby", "sort", "unique",
        "pallas_hash_partition", "pallas_segmented_scan", "groupby_scatter"}


def test_main_phase_agrees_with_pandas(main_phase):
    rec, exp_gb = main_phase
    assert rec["rows_per_side"] == ROWS and rec["sorted_rows"] == ROWS
    assert rec["groups"] == len(exp_gb) and rec["join_rows"] > 0


def test_forced_shuffle_keeps_the_rows(world1, data):
    # XLA:CPU has no RaggedAllToAll: the forced exchange is the bucketed one
    assert chip_smoke.forced_shuffle(world1, data, expect_ragged=False) == {
        "family": "bucketed", "rows": ROWS}


def test_wide_phase_agrees_with_pandas(world1):
    rec = chip_smoke.phase_wide(world1, ROWS, SEED)
    assert rec["dtypes"] == "int64/float64" and rec["sorted_rows"] == ROWS


def test_out_of_core_and_served_phases_agree_with_main(world1, data,
                                                       main_phase, tmp_path):
    rec, exp_gb = main_phase
    ooc = chip_smoke.phase_out_of_core(data, exp_gb, str(tmp_path))
    assert ooc["passes"] == chip_smoke.PASSES
    assert ooc["groups"] == rec["groups"]
    served = chip_smoke.phase_served(world1, data, rec["join_rows"], exp_gb,
                                     str(tmp_path))
    assert served["states"] == ["done"] * 3
    assert served["cache_hit"] == [False, False, True]


def test_sharded_phase_agrees_with_pandas(ctx4, data):
    rec = chip_smoke.phase_sharded(ctx4, data, expect_ragged=False)
    assert rec["world"] == 4 and rec["bytes_sent"] > 0


def test_chip_modes_are_refused_on_cpu():
    with pytest.raises(AssertionError):
        chip_smoke.assert_chip_modes(chip_smoke.realized_modes())


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_platform_check_exits_2_on_cpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                          *argv], env=env, cwd=ROOT, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 2, out.stderr[-2000:]
    assert '"ok"' not in out.stdout
    assert "'cpu', not 'tpu'" in out.stderr


def test_compile_cache_leaves_env_dir_alone(monkeypatch, tmp_path):
    from cylon_tpu.utils import compile_cache

    in_force = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.delenv("CYLON_TEST_NO_COMPILE_CACHE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_persistent_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == in_force
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_persistent_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        monkeypatch.setenv("CYLON_TEST_NO_COMPILE_CACHE", "1")
        assert compile_cache.enable_persistent_compile_cache() is None
    finally:
        jax.config.update("jax_compilation_cache_dir", in_force)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def test_ragged_probe_raises_instead_of_falling_back(ctx2, monkeypatch):
    """On XLA:CPU the shuffle is bucketed and nothing is probed; anywhere
    else a RaggedAllToAll that does not compile and run is an error with
    the compiler's message, not a quiet change of path."""
    from cylon_tpu.parallel import ops as par_ops

    assert par_ops._probe_ragged(ctx2) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception, match="(?i)ragged"):
        par_ops._probe_ragged(ctx2)


@pytest.mark.parametrize("case", ["2^22", "2^24", "2^24 hot"])
def test_large_shards_go_in_rounds_under_the_operand_limit(case):
    """2^22 rows a shard halt the v5e inside one RaggedAllToAll: they and
    more go in rounds, and each round's send operand and receive buffer
    stay under 2^31 bytes whatever the targets are; one row fewer goes
    whole, as it did."""
    import numpy as np

    from cylon_tpu.parallel import shuffle

    world, limit = 4, 1 << 31
    shard = 1 << (22 if case == "2^22" else 24)
    under = (1 << 22) - 1
    assert shuffle.ragged_round_quota(under, world) is None
    assert shuffle.plan_rounds(np.zeros((world, world), int),
                               under) == (1, under)
    quota = shuffle.ragged_round_quota(shard, world)
    assert world * quota * shuffle.RAGGED_ROW_BYTES == limit // 2
    live = shard - shard // 21        # 16,000,000 of 2^24
    if case.endswith("hot"):          # every source sends all it has to 0
        cm = np.zeros((world, world), int)
        cm[:, 0] = live
    else:
        cm = np.full((world, world), live // world)
    rounds, operand_rows = shuffle.plan_rounds(cm, shard)
    assert rounds == -(-cm.max() // quota)
    assert operand_rows == rounds * world * quota
    landed = np.zeros(world, int)
    for r in range(rounds):
        plans = [[np.asarray(x) for x in
                  shuffle.ragged_round_plan(cm, me, r, quota)]
                 for me in range(world)]
        moved = np.stack([send for send, _, _, _ in plans])
        for me, (send, recv, offsets, landing) in enumerate(plans):
            assert send.sum() * shuffle.RAGGED_ROW_BYTES < limit
            assert recv.sum() * shuffle.RAGGED_ROW_BYTES < limit
            # what the sources send is what this receiver expects, each
            # slice after the lower ranks', the buffer after earlier rounds'
            np.testing.assert_array_equal(recv, moved[:, me])
            np.testing.assert_array_equal(offsets, moved[:me].sum(axis=0))
            assert landing == landed[me]
            landed[me] += recv.sum()
    np.testing.assert_array_equal(landed, cm.sum(axis=0))
