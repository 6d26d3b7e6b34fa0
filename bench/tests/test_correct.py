"""``correct`` has to come out false when it should.  Each test skips the
harness's look for a chip and drives the rest of a run (``run_cell``) on
the CPU at a tiny size: sound, then with the timed path broken underneath
-- an answer altered where it is produced, half of the rows left out, the
exchange between chips left out -- and with the control, the plain
reference computed in bfloat16, put in the program's place.
"""
import time

import numpy as np
import pytest

from bench import run as run_mod

TINY_ROWS = 4096
SEED = 3000000019  # past 32 signed bits, as the driver's seeds are

# the registered cell, and the same configuration over four devices: no
# such cell is registered yet, and the harness has to be ready for one
FOUR = "test.join_gbs.4dev"
CELLS = {"join_gbs.uniform.1chip": 1, FOUR: 4}
# the CPU has no ragged exchange: a sound four-device run on it fails this
# one number and no other
CPU_ONLY = {"exchange_not_ragged"}


@pytest.fixture()
def tiny(monkeypatch):
    """Cells cut to a size a test can hold; the chip's peaks and memory
    statistics, which the CPU lacks, stubbed."""
    load_benchmark, load = run_mod.load_benchmark, run_mod.load_cell

    def with_four():
        bench = load_benchmark()
        one = next(w for w in bench["workloads"]
                   if w["name"] == "join_gbs.uniform.1chip")
        bench["workloads"].append(dict(one, name=FOUR, chips=4))
        return bench

    def load_tiny(workload):
        cell = load(workload)
        cell.cfg["rows_per_side_by_chips"] = {"1": TINY_ROWS, "4": TINY_ROWS}
        cell.cfg["table_capacity"] = None
        return cell

    monkeypatch.setattr(run_mod, "load_benchmark", with_four)
    monkeypatch.setattr(run_mod, "load_cell", load_tiny)
    monkeypatch.setattr(run_mod, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 1.0})
    monkeypatch.setattr(run_mod, "memory_peak_bytes", lambda devices: 1)
    return load_tiny


def drive(workload, seconds=0.5):
    import jax

    return run_mod.run_cell(workload, SEED, seconds, False,
                            jax.devices()[:CELLS[workload]],
                            time.perf_counter())


def failing(result):
    return {n for n, (v, limit) in result["compared"].items() if v > limit}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(tiny, workload):
    result = drive(workload)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert failing(result) <= CPU_ONLY
    assert result["correct"] == (not failing(result))
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_altered_answer_is_not_correct(tiny, monkeypatch, workload):
    """One value of each answer moved by a thousandth, where the driver
    hands the answer over."""
    driver = tiny(workload).driver
    fetch = driver.fetch

    def altered(table):
        out = fetch(table)
        out["sum_a"] = np.array(out["sum_a"])
        out["sum_a"][-1] *= 1.001
        return out

    monkeypatch.setattr(driver, "fetch", altered)
    result = drive(workload)
    assert not result["correct"]
    assert failing(result) - CPU_ONLY == {"sum_rel_err"}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_half_of_the_rows_left_out_is_not_correct(tiny, monkeypatch,
                                                  workload):
    """The program is given every other row of its largest table."""
    driver = tiny(workload).driver
    build = driver.build

    def halved(ctx, cfg, data):
        cut = dict(data, left={k: v[::2] for k, v in data["left"].items()})
        return build(ctx, cfg, cut)

    monkeypatch.setattr(driver, "build", halved)
    result = drive(workload)
    assert not result["correct"]
    assert "join_rows_off" in failing(result)


def test_exchange_left_out_is_not_correct(tiny, monkeypatch):
    """Four devices, and every shuffle hands its table back unmoved: keys
    meet only where they already shared a shard."""
    from cylon_tpu.parallel import ops as par_ops

    monkeypatch.setattr(par_ops, "_shuffled",
                        lambda t, key_idx, *a, **kw: t)
    result = drive(FOUR)
    assert not result["correct"]
    assert {"join_rows_off", "queries_without_exchange"} <= failing(result)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_fails_a_limit(tiny, workload):
    """The reference in bfloat16, in the program's place, has to fail at
    least one of the cell's numbers, on three seeds; the reference in its
    own place fails none."""
    from bench.limits import control_numbers

    cell = tiny(workload)
    ref, limits = cell.reference, cell.cfg["limits"]
    assert cell.cfg["control_precision"] == "bf16"
    for seed in (SEED, SEED + 1, SEED + 2):
        numbers = control_numbers(cell, seed)
        assert any(v > limits[n] for n, v in numbers.items()), (seed, numbers)
    data = ref.make_data(cell.cfg, cell.chips, SEED)
    for query in ref.queries(cell.cfg, SEED):
        exp = ref.answer(data, query)
        assert all(v <= limits[n] for n, v in ref.compare(exp, exp).items())
