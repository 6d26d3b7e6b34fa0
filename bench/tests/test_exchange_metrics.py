"""The four-chip cell's own per-layer metrics on a hand-made run: the
readers divide by the right thing, and a program that lacks the counter or
a trace that holds no collective gives nothing and does not raise."""
import json
import os
from types import SimpleNamespace

import pytest

from bench.run import load_benchmark, load_reader
from bench.tests.conftest import ROOT

CELL = "join_gbs.uniform.4chip"
NAMES = ("exchange.collective_ms_per_query", "exchange.ici_roofline_pct",
         "exchange.rounds_per_query", "exchange.operand_bytes_per_row",
         "entry.fetch_h2d_mb_per_query")
PEAKS = {"ici_bits_per_s": 1.6e12}


def run(**kw):
    base = dict(trace={}, counters={}, spans={}, records=[], work_bytes=[],
                peaks=PEAKS)
    base.update(kw)
    return SimpleNamespace(**base)


def read(name, **kw):
    return load_reader(name)(run(**kw))


def test_the_cell_lists_its_five_metrics_and_no_other_cell_does():
    per_layer = {m["name"]: m for m in load_benchmark()["per_layer"]}
    for name in NAMES:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "rows_per_s"
    with open(os.path.join(
            ROOT, "bench", "configs", "cylon_join_scaling_4chip.json")) as f:
        cfg = json.load(f)
    assert cfg["rows_per_side_by_chips"] == {"4": 64_000_000}
    assert cfg["table_capacity"] == 4 << 24 and cfg["reduced"] == {}


def test_readers_on_a_hand_made_run():
    trace = {"queries": 3, "chips": 4,
             "categories_s": {"collective": 0.6, "other": 30.0}}
    # 4e9 B of work: three quarters leave their chip, a quarter of that a
    # chip, at 2e11 B/s: 3.75 ms least, against 200 ms of collectives
    assert read("exchange.collective_ms_per_query", trace=trace) == \
        pytest.approx(200.0)
    assert read("exchange.ici_roofline_pct", trace=trace,
                work_bytes=[4e9, 4e9]) == pytest.approx(1.875)
    counters = {"queries": 4, "shuffle.rounds": 136,
                "shuffle.operand_bytes": 5.0e11, "table.fetch.h2d_bytes": 3.6e9}
    records = [{"ok": True, "rows": 128_000_000}] * 4 + [
        {"ok": False, "rows": 128_000_000}]
    assert read("exchange.rounds_per_query", counters=counters) == 34
    assert read("exchange.operand_bytes_per_row", counters=counters,
                records=records) == pytest.approx(5.0e11 / 5.12e8)
    assert read("entry.fetch_h2d_mb_per_query", counters=counters,
                spans={"obs.root": (1.0, 4)}) == pytest.approx(900.0)
    # fetches that upload nothing are a measured 0
    assert read("entry.fetch_h2d_mb_per_query", counters={"queries": 4},
                spans={"obs.root": (1.0, 4)}) == 0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_or_a_trace_without_the_source_gives_nothing(name):
    assert read(name) is None
    assert read(name, trace={"queries": 3, "chips": 4,
                             "categories_s": {"other": 1.0}},
                counters={"queries": 0}, work_bytes=[4e9]) is None


def test_the_registered_cell_runs_through_the_harness_at_a_tiny_size(
        monkeypatch):
    """The configuration file as the harness loads it, cut to 4096 rows on
    four CPU devices: sound except for the one number the CPU cannot meet
    (it has no ragged exchange)."""
    import time

    import jax

    from bench import run as run_mod

    load = run_mod.load_cell

    def load_tiny(workload):
        cell = load(workload)
        assert cell.chips == 4 and cell.cfg["driver"] == "join_gbs"
        cell.cfg["rows_per_side_by_chips"] = {"4": 4096}
        cell.cfg["table_capacity"] = None
        return cell

    monkeypatch.setattr(run_mod, "load_cell", load_tiny)
    monkeypatch.setattr(run_mod, "peaks_for", lambda kind: dict(PEAKS))
    monkeypatch.setattr(run_mod, "memory_peak_bytes", lambda devices: 1)
    result = run_mod.run_cell(CELL, 3000000019, 0.5, False,
                              jax.devices()[:4], time.perf_counter())
    assert result["attempted"] > 0 and result["failed"] == 0
    failing = {n for n, (v, limit) in result["compared"].items() if v > limit}
    assert failing == {"exchange_not_ragged"}
    assert set(result["metrics"]) == {"rows_per_s", "setup_s"}
