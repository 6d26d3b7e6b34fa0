"""TPC-H Q5 through the planner (ISSUE 31): the benchmark driver's logical
plan against the benchmark's plain reference on seeded data at SF 0.01-0.02,
on one shard and on the four-device mesh, and the two mechanisms the
deployment forced, as counts: a stage is one cached program named
``plan_*`` on one shard as on many, and a predicate's literals are its
operands, so a (REGION, DATE) nobody sent before builds no stage program.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.monitoring

from bench.drivers import tpch_q5 as driver
from bench.references import tpch_q5 as ref
from cylon_tpu import Table
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import spans as obs_spans
from cylon_tpu.plan import col, lit
from cylon_tpu.table import _cap_round as _cap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "bench", "configs", "tpch_q5.json")) as f:
    CONFIG = json.load(f)
LIMITS = CONFIG["limits"]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiled = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: _compiled.append(
        kw.get("fun_name", "?")) if event == COMPILE_EVENT else None)


def _deployment(ctx, scale_factor, seed):
    """The benchmark's tables at a small scale, in buffers of their own
    row counts, and the seed's cycle of five substitutions."""
    cfg = dict(CONFIG, scale_factor=scale_factor,
               table_capacity=dict.fromkeys(CONFIG["table_capacity"]))
    data = ref.make_data(cfg, 1, seed)
    return data, driver.build(ctx, cfg, data), ref.queries(cfg, seed)


def _counter(name):
    return obs_metrics.snapshot()["counters"].get(name, 0)


@pytest.fixture(scope="module")
def one_shard(local_ctx):
    return _deployment(local_ctx, 0.01, 11)


@pytest.fixture(scope="module")
def four_shards(ctx4):
    return _deployment(ctx4, 0.01, 11)


@pytest.mark.parametrize("qi", range(5))
@pytest.mark.parametrize("world", ["one_shard", "four_shards"])
def test_q5_agrees_with_the_plain_reference(request, world, qi):
    data, state, cycle = request.getfixturevalue(world)
    got = driver.fetch(driver.run(state, cycle[qi]))
    exp = ref.answer(data, cycle[qi])
    assert len(exp["n_name"]) == 5
    compared = ref.compare(got, exp)
    assert compared == {"nations_wrong": 0, "order_wrong": 0,
                        "revenue_rel_err": compared["revenue_rel_err"]}
    # wide accumulation here: float64 sums of float64 products
    assert compared["revenue_rel_err"] < 1e-12 < LIMITS["revenue_rel_err"]
    control = ref.compare(ref.answer(data, cycle[qi], "bf16"), exp)
    assert control["revenue_rel_err"] > 10 * LIMITS["revenue_rel_err"]
    assert control["nations_wrong"] == 0


def test_first_query_compiles_few_programs_and_a_new_substitution_none(
        local_ctx):
    """SF 0.02 and seed 22: the capacities (``_cap_round`` of the year's
    orders and of each join's count) agree across the five substitutions,
    so whatever a later one compiled would be a stage keyed by a literal.
    The parent compiled 93 programs for the first query: every primitive
    of every stage was a program of its own on one shard."""
    data, state, cycle = _deployment(local_ctx, 0.02, 22)
    caps = {tuple(_cap(rows[k]) for k in (
        "orders_in_year", "lines_joined", "lines_local"))
        for rows in (ref.answer(data, q)["stage_rows"] for q in cycle)}
    assert len(caps) == 1, caps
    before = len(_compiled)
    first = driver.fetch(driver.run(state, cycle[0]))
    assert 0 < len(_compiled) - before <= 30, _compiled[before:]
    assert ref.compare(first, ref.answer(data, cycle[0]))["nations_wrong"] == 0
    before, misses = len(_compiled), _counter("plan_cache.miss")
    stages, operands = (_counter("plan.stage_programs"),
                        _counter("plan.literal_operands"))
    for query in cycle[1:]:
        got = driver.fetch(driver.run(state, query))
        assert ref.compare(got, ref.answer(data, query)) == {
            "nations_wrong": 0, "order_wrong": 0,
            "revenue_rel_err": pytest.approx(0, abs=1e-12)}
    assert _compiled[before:] == []
    assert _counter("plan_cache.miss") == misses
    # two filters, the join's count and the fused stage a query; the date
    # range's two literals, the region's name and the revenue's 1.0
    assert _counter("plan.stage_programs") - stages == 4 * 4
    assert _counter("plan.literal_operands") - operands == 4 * 4


@pytest.mark.parametrize("world", ["one_shard", "four_shards"])
def test_a_substitution_never_sent_misses_no_stage_program(request, world):
    """Under the parent's key (``pred.spec()``, the literals' values) a new
    (REGION, DATE) built three stage programs on four shards."""
    data, state, cycle = request.getfixturevalue(world)
    sent = cycle[0]
    driver.fetch(driver.run(state, sent))
    # another region, the range a day later: a pair nobody sent, whose
    # counts land in the capacities of the one that was
    fresh = dict(sent, region_key=(sent["region_key"] + 1) % 5,
                 date_lo=sent["date_lo"] + 1, date_hi=sent["date_hi"] + 1)
    fresh["region"] = ref.REGIONS[fresh["region_key"]]
    exp = ref.answer(data, fresh)
    rows = ref.answer(data, sent)["stage_rows"]
    assert all(_cap(exp["stage_rows"][k]) == _cap(rows[k]) for k in (
        "orders_in_year", "lines_joined", "lines_local"))
    misses, hits = _counter("plan_cache.miss"), _counter("plan_cache.hit")
    got = driver.fetch(driver.run(state, fresh))
    assert _counter("plan_cache.miss") == misses
    assert _counter("plan_cache.hit") - hits >= 4
    assert ref.compare(got, exp)["nations_wrong"] == 0


def test_the_driver_counts_stage_programs_query_by_query(one_shard):
    """One query that launched eight stage programs does not cover for
    another that launched none."""
    data, state, cycle = one_shard
    driver.run(state, cycle[0])
    assert state["stage_programs"][-1] == driver.STAGES_A_QUERY
    assert driver.structure(state, 1, {}) == {
        "queries_without_stage_programs": 0}
    uneven = dict(state, stage_programs=[8, 0, 4])
    assert driver.structure(uneven, 1, {}) == {
        "queries_without_stage_programs": 1}


def test_a_filter_stage_is_one_program_named_plan(local_ctx):
    rng = np.random.default_rng(5)
    t = Table.from_numpy(["k", "v"], [
        rng.integers(0, 50, 3000).astype(np.int32),
        rng.random(3000)], ctx=local_ctx)
    t.row_count  # nothing of the table's own is left to compile
    before = len(_compiled)
    out = t.plan().filter((col("k") >= 10) & (col("v") < lit(0.5))).execute()
    jax.block_until_ready(out.columns)
    assert _compiled[before:] == ["jit(plan_filter)"]
    spans = [e for e in obs_spans.ring_events() if e.name == "plan.stage"]
    assert spans and spans[-1].attrs["kind"] == "filter"
    assert spans[-1].attrs["program"] == "plan_filter"
    # other literals, the same program
    before = len(_compiled)
    out2 = t.plan().filter((col("k") >= 40) & (col("v") < lit(0.25))
                           ).execute()
    assert _compiled[before:] == []
    k, v = (np.asarray(a) for a in t.to_numpy().values())
    got = out2.to_numpy()
    keep = (k >= 40) & (v < 0.25)
    assert np.array_equal(got["k"], k[keep]) and np.array_equal(
        got["v"], v[keep])


def test_a_derive_stage_is_a_program_with_a_span(local_ctx):
    t = Table.from_numpy(["v"], [np.arange(64, dtype=np.float64)],
                         ctx=local_ctx)
    before = _counter("plan.stage_programs")
    out = t.plan().with_column("w", lit(2.0) - col("v") * 3).execute()
    assert _counter("plan.stage_programs") - before == 1
    assert np.array_equal(out.to_numpy()["w"], 2.0 - np.arange(64.0) * 3)
    last = [e for e in obs_spans.ring_events() if e.name == "plan.stage"][-1]
    assert last.attrs["kind"] == "derive"
    assert last.attrs["program"] == "plan_derive"


def test_shape_leaves_a_literals_value_out_and_spec_keeps_it():
    a = (col("d") >= 731) & (col("d") < 1096)
    b = (col("d") >= 366) & (col("d") < 731)
    assert a.shape() == b.shape() and a.spec() != b.spec()
    assert [node.value for node in a.literals()] == [731, 1096]
    # a literal's type is part of the program
    assert (col("d") >= 1).shape() != (col("d") >= 1.0).shape()
    # a compared string is an operand too, as its packed words: names of
    # most lengths are one program
    s, m = col("n") == "ASIA", col("n") == "MIDDLE EAST"
    assert s.shape() == m.shape() and s.spec() != m.spec()
    assert [node.value for node in s.literals()] == ["ASIA"]
    assert s.shape() != (col("n") == "A" * 33).shape()
    # a divisor is checked when the stage is traced: it stays in the shape
    # with its value and is no operand
    q = (col("x") / 4.0) * 2.0
    assert [node.value for node in q.literals()] == [2.0]
    assert q.shape() != ((col("x") / 5.0) * 2.0).shape()
    assert q.shape() == ((col("x") / 4.0) * 3.0).shape()


@pytest.mark.parametrize("world", ["local_ctx", "ctx4"])
def test_one_lit_in_two_places_binds_by_position(request, world):
    """``x = lit(5); (a >= x) & (b < x)`` holds one ``Lit`` object twice.
    The next predicate of the same shape has two values there: it runs the
    first one's cached program, and each place has to read its own."""
    import pandas as pd

    ctx = request.getfixturevalue(world)
    rng = np.random.default_rng(3)
    df = pd.DataFrame({"a": rng.integers(0, 12, 4000).astype(np.int32),
                       "b": rng.integers(0, 12, 4000).astype(np.int32)})
    t = Table.from_pandas(df, ctx=ctx)

    def rows(pred):
        got = t.plan().filter(pred).execute().to_pandas()
        return got.sort_values(["a", "b"]).reset_index(drop=True)

    def expect(mask):
        return df[mask].sort_values(["a", "b"]).reset_index(drop=True)

    x = lit(5)
    shared = (col("a") >= x) & (col("b") < x)
    two = (col("a") >= 3) & (col("b") < 9)
    assert shared.shape() == two.shape()
    pd.testing.assert_frame_equal(rows(shared),
                                  expect((df.a >= 5) & (df.b < 5)))
    misses = _counter("plan_cache.miss")
    pd.testing.assert_frame_equal(rows(two), expect((df.a >= 3) & (df.b < 9)))
    assert _counter("plan_cache.miss") == misses  # the same program
    # and the other way round: two values first, then one object twice
    y = lit(7.5)
    pd.testing.assert_frame_equal(
        rows((col("a") * 1.0 >= 2.5) | (col("b") * 1.0 < 1.5)),
        expect((df.a >= 2.5) | (df.b < 1.5)))
    pd.testing.assert_frame_equal(
        rows((col("a") * 1.0 >= y) | (col("b") * 1.0 < y)),
        expect((df.a >= 7.5) | (df.b < 7.5)))
    # a derive with the literal on either side of the column
    z = lit(2)
    out = t.plan().with_column("w", (z - col("a")) * z + col("b") * 3
                               ).execute().to_pandas()
    out2 = t.plan().with_column("w", (lit(4) - col("a")) * 5 + col("b") * 6
                                ).execute().to_pandas()
    key = ["a", "b", "w"]
    pd.testing.assert_frame_equal(
        out.sort_values(key).reset_index(drop=True)[key],
        df.assign(w=(2 - df.a) * 2 + df.b * 3).sort_values(key)
        .reset_index(drop=True)[key], check_dtype=False)
    pd.testing.assert_frame_equal(
        out2.sort_values(key).reset_index(drop=True)[key],
        df.assign(w=(4 - df.a) * 5 + df.b * 6).sort_values(key)
        .reset_index(drop=True)[key], check_dtype=False)


@pytest.mark.parametrize("world", ["local_ctx", "ctx4"])
def test_a_compared_string_is_an_operand(request, world):
    """A name nobody sent before runs the program of the first one, on
    every comparison; a name longer than the column compares as the eager
    layer does."""
    import pandas as pd

    ctx = request.getfixturevalue(world)
    names = np.array(ref.REGIONS + ["", "ASIA MINOR", "AS"], object)
    df = pd.DataFrame({"n": names[np.random.default_rng(8).integers(
        0, len(names), 500)], "i": np.arange(500, dtype=np.int32)})
    t = Table.from_pandas(df, ctx=ctx)

    def ids(pred):
        got = t.plan().filter(pred).execute().to_numpy()
        return sorted(np.asarray(got["i"]).tolist())

    assert ids(col("n") == "ASIA") == df.i[df.n == "ASIA"].tolist()
    misses = _counter("plan_cache.miss")
    for name in ["MIDDLE EAST", "EUROPE", "", "NOWHERE", "ASIA MINOR AND MORE"]:
        assert ids(col("n") == name) == df.i[df.n == name].tolist(), name
    assert _counter("plan_cache.miss") == misses
    for op, want in [("lt", df.n < "ASIA"), ("ge", df.n >= "ASIA"),
                     ("ne", df.n != "ASIA")]:
        pred = {"lt": col("n") < "ASIA", "ge": lit("ASIA") <= col("n"),
                "ne": col("n") != "ASIA"}[op]
        assert ids(pred) == df.i[want].tolist(), op
    with pytest.raises(Exception, match="cannot compare"):
        t.plan().filter(col("i") == "ASIA").execute()


def test_a_plans_fingerprint_keeps_the_values(local_ctx):
    t = Table.from_numpy(["d"], [np.arange(32, dtype=np.int32)],
                         ctx=local_ctx)
    assert (t.plan().filter(col("d") >= 3).fingerprint()
            != t.plan().filter(col("d") >= 4).fingerprint())
