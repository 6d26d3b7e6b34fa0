"""A foreign-key join under skew (ISSUE 33): the benchmark's driver
``join_gbs`` on configuration ``cylon_join_zipf`` -- the fact side's keys
Zipf 1.25 over a dimension that holds every key once -- cut to 2^14-2^16
rows, against the benchmark's plain reference, on one shard and on the
four-device mesh; what the reference's generator promises; the two counters
``_local_join`` keeps of how full a join's slots are, with no host sync
added; and the two readers the cell brings.
"""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import trace_reduce
from bench.drivers import join_gbs as driver
from bench.references import join_gbs as uniform_ref
from bench.references import join_gbs_zipf as ref
from bench.run import load_reader
from cylon_tpu import config
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import spans as obs_spans
from cylon_tpu.table import _cap_round

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "bench", "configs", "cylon_join_zipf.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "bench", "configs",
                       "cylon_join_scaling.json")) as f:
    UNIFORM = json.load(f)
LIMITS = CONFIG["limits"]
# (rows a side, seed): the last seed is past 32 signed bits, as the driver's
SIZES = [(1 << 14, 5), (1 << 15, 1234567), (1 << 16, 3000000019)]
FILL = "kernels.join_slot_fill_pct"
GATHER = "kernels.gather_ms_per_query"


def _cfg(rows):
    return dict(CONFIG, rows_per_side_by_chips={"1": rows, "4": rows},
                table_capacity=None)


def _harmonic(n, s):
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -s))


def _counters(*names):
    got = obs_metrics.snapshot()["counters"]
    return [got.get(n, 0) for n in names]


# ---------------------------------------------------------------------------
# the cell's query against its plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,seed", SIZES)
@pytest.mark.parametrize("world", ["local_ctx", "ctx4"])
def test_zipf_cell_agrees_with_the_plain_reference(request, world, rows,
                                                   seed):
    ctx = request.getfixturevalue(world)
    cfg = _cfg(rows)
    data = ref.make_data(cfg, 1, seed)
    state = driver.build(ctx, cfg, data)
    (query,) = ref.queries(cfg, seed)
    got = driver.fetch(driver.run(state, query))
    exp = ref.answer(data, query)
    assert exp["join_rows"] == rows == ref.input_rows(data, query) // 2
    compared = ref.compare(got, exp)
    assert set(compared) == set(uniform_ref.compare(got, exp)) | {
        "hot_sum_rel_err", "hot_mean_rel_err"}
    assert all(value <= LIMITS[name] for name, value in compared.items()), \
        compared
    # the hottest key's group is the answer's first row: key 0, a fifth
    # and more of the join
    assert got["l_k"][0] == 0
    assert got["count_a"][0] == np.sum(data["left"]["k"] == 0) > rows // 5
    # the control, in the program's place, fails the cell
    control = ref.compare(ref.answer(data, query, "bf16"), exp)
    assert any(value > LIMITS[name] for name, value in control.items())


def test_no_limit_is_wider_than_the_uniform_cells():
    for name, limit in UNIFORM["limits"].items():
        assert LIMITS[name] == limit
    assert LIMITS["hot_sum_rel_err"] == LIMITS["sum_rel_err"]
    assert LIMITS["hot_mean_rel_err"] == LIMITS["mean_rel_err"]
    for key in ("tables", "query", "rows_per_side_by_chips",
                "table_capacity", "guarantees", "precision",
                "control_precision", "driver"):
        assert CONFIG[key] == UNIFORM[key], key
    assert CONFIG["reduced"] == {} and CONFIG["architecture"] is None


# ---------------------------------------------------------------------------
# what make_data promises
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def big():
    """2^20 rows a side: NumPy alone, and enough draws for the shares."""
    rows = 1 << 20
    return rows, ref.make_data(_cfg(rows), 1, 7)


def test_right_keys_are_a_permutation_and_every_left_key_matches(big):
    rows, data = big
    assert np.array_equal(np.sort(data["right"]["k"]), np.arange(rows))
    assert not np.array_equal(data["right"]["k"], np.arange(rows))
    left = data["left"]["k"]
    assert left.min() == 0 and left.max() < rows
    assert {c.dtype for c in data["left"].values()} == {
        np.dtype("int64"), np.dtype("float64")}
    assert {len(c) for side in data.values() for c in side.values()} == {rows}


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_hot_keys_hold_the_shares_of_zipf_1p25(seed):
    rows = 1 << 20
    counts = np.bincount(ref.make_data(_cfg(rows), 1, seed)["left"]["k"],
                         minlength=rows)
    h = _harmonic(rows, 1.25)
    assert counts[0] == counts.max()
    assert counts[0] / rows == pytest.approx(1 / h, rel=0.01)
    assert counts[:10].sum() / rows == pytest.approx(
        _harmonic(10, 1.25) / h, rel=0.01)
    # ranks in order of weight: each of the first few keys outdraws the next
    assert np.all(np.diff(counts[:6]) < 0)


def test_the_literatures_high_skew_at_the_cells_size():
    """What the configuration's ``assumed`` recalls, from the weights
    alone: at 16,000,000 keys and s = 1.25 the hottest key holds 22.07%
    and the top ten 52.4% (Blanas et al.: 52%)."""
    h = _harmonic(16_000_000, CONFIG["zipf_s"])
    assert 1 / h == pytest.approx(0.2207, abs=5e-5)
    assert _harmonic(10, 1.25) / h == pytest.approx(0.524, abs=5e-4)


def test_same_seed_same_data_and_the_draw_order(big):
    rows, data = big
    again = ref.make_data(_cfg(rows), 1, 7)
    other = ref.make_data(_cfg(rows), 1, 8)
    for side in ("left", "right"):
        for name, column in data[side].items():
            assert np.array_equal(column, again[side][name])
            assert not np.array_equal(column, other[side][name])
    # left keys, left values, right keys, right values: one uniform draw a
    # key, then join_gbs.make_data's order
    rng = np.random.default_rng(7)
    weights = np.cumsum(np.arange(1, rows + 1, dtype=np.float64) ** -1.25)
    keys = np.searchsorted(weights / weights[-1], rng.random(rows),
                           side="right")
    assert np.array_equal(data["left"]["k"], keys)
    assert np.array_equal(data["left"]["a"], rng.random(rows))
    assert np.array_equal(data["right"]["k"], rng.permutation(rows))
    assert np.array_equal(data["right"]["b"], rng.random(rows))


def test_compare_reads_the_hot_group_on_its_own():
    exp = {"l_k": np.array([0, 5, 9]), "count_a": np.array([7, 2, 1]),
           "sum_a": np.array([3.5, 1.0, 0.25]),
           "mean_a": np.array([0.5, 0.5, 0.25]), "join_rows": 10}
    assert set(ref.compare(exp, exp).values()) == {0}
    tail = dict(exp, sum_a=exp["sum_a"] * np.array([1, 1, 1.01]))
    got = ref.compare(tail, exp)
    assert got["sum_rel_err"] == pytest.approx(0.01)
    assert got["hot_sum_rel_err"] == got["hot_mean_rel_err"] == 0
    hot = dict(exp, sum_a=exp["sum_a"] * np.array([1.001, 1, 1]),
               mean_a=exp["mean_a"] * np.array([0.999, 1, 1]))
    got = ref.compare(hot, exp)
    assert got["hot_sum_rel_err"] == got["sum_rel_err"] == \
        pytest.approx(0.001)
    assert got["hot_mean_rel_err"] == pytest.approx(0.001)


# ---------------------------------------------------------------------------
# join.out_rows / join.out_slots, and the join.gather span
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world,shards", [("local_ctx", 1), ("ctx4", 4)])
def test_join_counts_its_rows_and_slots_with_no_added_host_sync(
        request, world, shards):
    rows, seed = 1 << 14, 11
    cfg = _cfg(rows)
    data = ref.make_data(cfg, 1, seed)
    state = driver.build(request.getfixturevalue(world), cfg, data)
    names = ("join.out_rows", "join.out_slots", "host.syncs")
    driver.run(state, {})                     # count pass, then the gather
    first = _counters(*names)
    driver.run(state, {})                     # the remembered capacity
    second = _counters(*names)
    with config.knob_env(CYLON_TPU_TRACE="1"):
        before = len(obs_spans.events())
        driver.run(state, {})
        gathers = [e for e in obs_spans.events()[before:]
                   if e.name == "join.gather"]
    third = _counters(*names)
    # every fact row meets one dimension row: the join has ``rows`` rows
    # wherever the exchange put them, in the fullest shard's capacity each
    steady = [b - a for a, b in zip(second, third)]
    assert [b - a for a, b in zip(first, second)] == steady
    assert steady[0] == rows
    assert steady[1] % shards == 0 and steady[1] >= rows
    if shards == 1:
        assert steady[1] == _cap_round(rows)
        # the capacity check and nothing else, as before the counters
        assert steady[2] == 1
    (span,) = gathers
    assert span.attrs == {"slots": steady[1], "rows": rows}
    run = SimpleNamespace(counters={"join.out_rows": steady[0],
                                    "join.out_slots": steady[1]})
    assert load_reader(FILL)(run) == pytest.approx(
        100.0 * rows / steady[1])


def test_a_truncated_gather_is_not_a_join_that_ran(local_ctx):
    """A remembered capacity that proves too small gathers twice; the
    counters hold the join once, at the capacity that held it."""
    from cylon_tpu import Table
    from cylon_tpu.context import ctx_cache

    def tables(n):
        k = np.zeros(n, np.int32)
        return (Table.from_numpy(["k", "a"], [k, np.ones(n)], ctx=local_ctx,
                                 capacity=64),
                Table.from_numpy(["k", "b"], [k, np.ones(n)], ctx=local_ctx,
                                 capacity=64))

    left, right = tables(4)
    assert left.distributed_join(right, on="k").row_count == 16
    before = _counters("join.out_rows", "join.out_slots")
    left, right = tables(9)                   # same site, 81 rows
    assert left.distributed_join(right, on="k").row_count == 81
    rows, slots = (b - a for a, b in zip(
        before, _counters("join.out_rows", "join.out_slots")))
    assert (rows, slots) == (81, _cap_round(81))
    assert _cap_round(81) in ctx_cache(local_ctx, "_join_cap_cache").values()


# ---------------------------------------------------------------------------
# the two readers
# ---------------------------------------------------------------------------

def test_fill_reader_on_a_hand_made_run_and_without_the_counters():
    read = load_reader(FILL)
    run = SimpleNamespace(counters={"queries": 12,
                                    "join.out_rows": 12 * 16_000_000,
                                    "join.out_slots": 12 * (1 << 24)})
    assert read(run) == pytest.approx(95.367431640625)
    # the parent of the PR that added the counters, and a window in which
    # no join ran: nothing to read, and no error
    assert read(SimpleNamespace(counters={"queries": 12,
                                          "host.syncs": 12})) is None
    assert read(SimpleNamespace(counters={})) is None
    assert read(SimpleNamespace(counters={"join.out_slots": 0})) is None


def test_gather_reader_on_a_hand_made_trace():
    read = load_reader(GATHER)
    ops = {
        "jit_join_gather/%fusion.7 fusion:kCustom s32[16777216]": 0.9,
        "jit_hash_groupby/%fusion fusion:kCustom f32[16777216]": 0.3,
        "jit_rfn/%fusion.2 fusion:kCustom u32[16777216,3]": 0.6,
        "jit_plan_filter/%fusion.1 fusion:kCustom pred[16777216]": 0.2,
        # none of these is a gather
        "jit_join_gather/%sort.1 sort tuple": 5.0,
        "jit_join_gather/%broadcast_clamp_fusion fusion:kLoop tuple": 5.0,
        "jit_join_gather/%fusion.32 fusion:kLoop tuple": 5.0,
        "jit_join_gather/%scatter_fusion fusion:kCustom s32[16]": 5.0,
        "jit_hash_groupby/%_segmented_scan_padded "
        "custom-call:tpu_custom_call tuple": 5.0,
    }
    run = SimpleNamespace(trace={"ops_s": ops, "queries": 4})
    assert read(run) == pytest.approx(500.0)
    only_sorts = {k: v for k, v in ops.items() if " sort " in k}
    assert read(SimpleNamespace(trace={"ops_s": only_sorts,
                                       "queries": 4})) == 0
    # an untraced run
    assert read(SimpleNamespace(trace={})) is None
    assert read(SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("fixture,gathers", [
    ("join_1chip_query", 23), ("join_4chip_query", None)])
def test_gather_reader_on_a_recorded_chip_trace(fixture, gathers):
    """PR 24's one-chip query: 23 gathers (seventeen lanes of the join and
    the six of the group-by and the fetch's ``_take``), 6.703 of its 7.208
    busy seconds; every ``fusion:kCustom`` of both recordings is one."""
    with open(os.path.join(ROOT, "bench", "fixtures",
                           fixture + ".events.json")) as f:
        reduced = trace_reduce.reduce(json.load(f))
    custom = {name: secs for name, secs in reduced["ops_s"].items()
              if " fusion:kCustom " in name}
    got = load_reader(GATHER)(SimpleNamespace(trace=reduced))
    assert got == pytest.approx(
        sum(custom.values()) / reduced["queries"] * 1e3)
    assert 0 < got < reduced["busy_s"] / reduced["queries"] * 1e3
    if gathers is not None:
        assert len(custom) == gathers
        assert got == pytest.approx(6703.156219)
