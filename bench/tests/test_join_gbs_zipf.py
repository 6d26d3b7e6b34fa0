"""``join_gbs.zipf.1chip`` through ``run_cell`` on the CPU at a tiny size:
a sound run is correct under the configuration's own limits, and the sum of
the hottest key's group moved by a thousandth, where the driver hands the
answer over, fails ``hot_sum_rel_err`` beside ``sum_rel_err`` and nothing
else; the bfloat16 control fails the cell.
"""
import time

import numpy as np
import pytest

from bench import run as run_mod
from bench.limits import control_numbers

CELL = "join_gbs.zipf.1chip"
TINY_ROWS = 4096
SEED = 3000000019


@pytest.fixture()
def tiny(monkeypatch):
    load = run_mod.load_cell

    def load_tiny(workload):
        cell = load(workload)
        cell.cfg["rows_per_side_by_chips"] = {"1": TINY_ROWS}
        cell.cfg["table_capacity"] = None
        return cell

    monkeypatch.setattr(run_mod, "load_cell", load_tiny)
    monkeypatch.setattr(run_mod, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 1.0})
    monkeypatch.setattr(run_mod, "memory_peak_bytes", lambda devices: 1)
    return load_tiny


def drive():
    import jax

    return run_mod.run_cell(CELL, SEED, 0.5, False, jax.devices()[:1],
                            time.perf_counter())


def failing(result):
    return {n for n, (v, limit) in result["compared"].items() if v > limit}


def test_sound_run_is_correct(tiny):
    result = drive()
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] and not failing(result)
    assert {"hot_sum_rel_err", "hot_mean_rel_err"} <= set(result["compared"])
    assert set(result["metrics"]) == {"rows_per_s", "setup_s"}


def test_wrong_hot_sum_is_not_correct(tiny, monkeypatch):
    driver = tiny(CELL).driver
    fetch = driver.fetch

    def altered(table):
        out = fetch(table)
        out["sum_a"] = np.array(out["sum_a"])
        out["sum_a"][0] *= 1.001
        return out

    monkeypatch.setattr(driver, "fetch", altered)
    result = drive()
    assert not result["correct"]
    assert failing(result) == {"sum_rel_err", "hot_sum_rel_err"}
    value, limit = result["compared"]["hot_sum_rel_err"]
    assert value == pytest.approx(0.001, rel=1e-3) and limit == 3e-5


def test_control_fails_the_cell(tiny):
    cell = tiny(CELL)
    limits = cell.cfg["limits"]
    for seed in (SEED, SEED + 1, SEED + 2):
        numbers = control_numbers(cell, seed)
        assert any(v > limits[n] for n, v in numbers.items()), numbers
