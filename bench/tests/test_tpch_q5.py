"""The TPC-H Q5 reference at a tiny scale -- the generator's rules, the
answer against a second way of computing it, the comparison, the bfloat16
control, ``stage_bytes`` -- and the five ``plan.*`` readers on a small
recorded run; a program without the counters gives nothing."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench.references import tpch_q5 as ref
from bench.run import load_json, load_reader
from bench.tests.conftest import ROOT

CFG = dict(load_json(ROOT, "bench", "configs", "tpch_q5.json"),
           scale_factor=0.01)
LIMITS = CFG["limits"]
READERS = ("plan.stage_device_ms_per_query", "plan.stage_roofline_pct",
           "plan.stage_programs_per_query", "plan.cache_misses_per_query",
           "plan.distinct_programs")


@pytest.fixture(scope="module")
def data():
    return ref.make_data(CFG, 1, 4000000007)


def test_the_generator_follows_the_population_rules(data):
    o, l, c, s = (data[t] for t in ("orders", "lineitem", "customer",
                                    "supplier"))
    assert len(o["o_orderkey"]) == 15000 and len(c["c_custkey"]) == 1500
    assert len(s["s_suppkey"]) == 100
    # sparse keys: the first 8 of every 32, from 1
    assert o["o_orderkey"][:10].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 33, 34]
    assert np.all((o["o_orderkey"] - 1) % 32 < 8)
    assert not np.any(o["o_custkey"] % 3 == 0)
    assert 1 <= o["o_custkey"].min() and o["o_custkey"].max() <= 1500
    assert 0 <= o["o_orderdate"].min() and o["o_orderdate"].max() <= 2405
    per_order = np.bincount(l["l_orderkey"])[o["o_orderkey"]]
    assert per_order.min() >= 1 and per_order.max() <= 7
    assert 1 <= l["l_suppkey"].min() and l["l_suppkey"].max() <= 100
    cents = np.round(l["l_extendedprice"] * 100)
    assert np.allclose(cents / 100, l["l_extendedprice"], rtol=0, atol=1e-9)
    assert 900.0 < l["l_extendedprice"].min()
    assert l["l_extendedprice"].max() <= 104950.0
    assert set(np.round(l["l_discount"] * 100).astype(int)) == set(range(11))
    assert l["l_extendedprice"].dtype == l["l_discount"].dtype == np.float64
    assert all(col.dtype == np.int32 for t in (o, c, s) for col in t.values())
    # the same seed, the same tables; another seed, others
    again = ref.make_data(CFG, 1, 4000000007)
    assert np.array_equal(again["lineitem"]["l_suppkey"], l["l_suppkey"])
    other = ref.make_data(CFG, 1, 4000000008)
    assert not np.array_equal(other["orders"]["o_orderdate"], o["o_orderdate"])


def test_the_cycle_is_every_region_and_every_year_once():
    cycle = ref.queries(CFG, 9)
    assert sorted(q["region"] for q in cycle) == sorted(ref.REGIONS)
    assert sorted(q["year"] for q in cycle) == ref.YEARS
    assert all(q["date_hi"] - q["date_lo"] in (365, 366) for q in cycle)
    assert ref.queries(CFG, 9) == cycle != ref.queries(CFG, 10)
    # 1993-01-01 is day 366 of an epoch that starts in a leap year
    assert {q["year"]: q["date_lo"] for q in cycle}[1993] == 366


def _by_hand(data, query):
    """Q5 by dense lookups, no merge: revenue by nation name."""
    c, o, l, s = (data[t] for t in ("customer", "orders", "lineitem",
                                    "supplier"))
    cust_nation = np.full(c["c_custkey"].max() + 1, -1)
    cust_nation[c["c_custkey"]] = c["c_nationkey"]
    supp_nation = np.full(s["s_suppkey"].max() + 1, -2)
    supp_nation[s["s_suppkey"]] = s["s_nationkey"]
    in_year = (o["o_orderdate"] >= query["date_lo"]) & (
        o["o_orderdate"] < query["date_hi"])
    order_nation = np.full(o["o_orderkey"].max() + 1, -3)
    order_nation[o["o_orderkey"][in_year]] = cust_nation[
        o["o_custkey"][in_year]]
    nation = order_nation[l["l_orderkey"]]
    region = np.asarray(ref.NATION_REGION + [-1])[nation]
    keep = (nation == supp_nation[l["l_suppkey"]]) & (
        region == query["region_key"])
    revenue = l["l_extendedprice"] * (1 - l["l_discount"])
    sums = np.bincount(nation[keep], revenue[keep], len(ref.NATIONS))
    return {ref.NATIONS[i]: v for i, v in enumerate(sums) if v}


def test_answer_compare_and_control(data):
    for query in ref.queries(CFG, 4000000007):
        exp = ref.answer(data, query)
        hand = _by_hand(data, query)
        assert dict(zip(exp["n_name"], exp["sum_revenue"])) == \
            pytest.approx(hand, rel=1e-12)
        assert np.all(np.diff(exp["sum_revenue"]) <= 0)
        assert ref.compare(exp, exp) == {
            "nations_wrong": 0, "order_wrong": 0, "revenue_rel_err": 0.0}
        # the control fails by the revenue alone, and by orders
        control = ref.compare(ref.answer(data, query, "bf16"), exp)
        assert control["nations_wrong"] == 0
        assert control["revenue_rel_err"] > 10 * LIMITS["revenue_rel_err"]
        # a nation left out, one twice, two out of order
        short = {k: v[1:] for k, v in exp.items() if k != "stage_rows"}
        assert ref.compare(short, exp)["nations_wrong"] == 1
        twice = {"n_name": np.append(exp["n_name"], exp["n_name"][-1]),
                 "sum_revenue": np.append(exp["sum_revenue"],
                                          exp["sum_revenue"][-1])}
        assert ref.compare(twice, exp)["nations_wrong"] == 1
        swapped = {k: np.concatenate([v[1::-1], v[2:]])
                   for k, v in short.items()}
        assert ref.compare(swapped, exp)["order_wrong"] == 1
    with pytest.raises(ValueError):
        ref.answer(data, query, "f16")


def test_input_rows_work_bytes_and_stage_bytes(data):
    query = ref.queries(CFG, 4000000007)[0]
    exp = ref.answer(data, query)
    lines = len(data["lineitem"]["l_orderkey"])
    assert ref.input_rows(data, query) == 1500 + 15000 + lines + 100 + 25 + 5
    assert ref.work_bytes(data, query, exp) == (
        1500 * 8 + 15000 * 12 + lines * 24 + 100 * 8 + 25 * (8 + 14)
        + 5 * (4 + 14) + len(exp["n_name"]) * (14 + 8))
    rows = exp["stage_rows"]
    assert rows["orders"] == 15000
    assert rows["orders"] > rows["orders_in_year"] > 0
    assert rows["lines_joined"] > rows["lines_local"] >= \
        rows["lines_in_region"] == int(np.sum(
            _lines_by_hand(data, query)))
    assert ref.stage_bytes(rows) == (
        15000 * 12 + rows["orders_in_year"] * 8
        + rows["lines_joined"] * 24 + rows["lines_local"] * 20
        + rows["lines_local"] * 34 + rows["lines_in_region"] * 30
        + rows["lines_in_region"] * 24)
    # it rides on the query's work_bytes, which is all the harness keeps
    work = ref.work_bytes(data, query, exp)
    assert work.stage_bytes == ref.stage_bytes(rows) and work + 0 == work


def _lines_by_hand(data, query):
    o, l = data["orders"], data["lineitem"]
    in_year = np.zeros(o["o_orderkey"].max() + 1, bool)
    in_year[o["o_orderkey"][(o["o_orderdate"] >= query["date_lo"])
                            & (o["o_orderdate"] < query["date_hi"])]] = True
    c, s = data["customer"], data["supplier"]
    cust = np.zeros(c["c_custkey"].max() + 1, int)
    cust[c["c_custkey"]] = c["c_nationkey"]
    order_cust = np.zeros(o["o_orderkey"].max() + 1, int)
    order_cust[o["o_orderkey"]] = o["o_custkey"]
    supp = np.zeros(s["s_suppkey"].max() + 1, int)
    supp[s["s_suppkey"]] = s["s_nationkey"]
    nation = cust[order_cust[l["l_orderkey"]]]
    return (in_year[l["l_orderkey"]] & (nation == supp[l["l_suppkey"]])
            & (np.asarray(ref.NATION_REGION)[nation] == query["region_key"]))


# ---- the five readers on a recorded run ----

with open(os.path.join(ROOT, "bench", "fixtures", "tpch_q5_run.json")) as f:
    RECORDED = json.load(f)


def _work_bytes():
    out = []
    for rows in RECORDED["stage_rows"]:
        out.append(ref.QueryBytes(1632402398))
        out[-1].stage_bytes = ref.stage_bytes(rows)
    return out


def _run(trace=None, counters=None, work_bytes=None):
    return SimpleNamespace(
        trace=RECORDED["trace"] if trace is None else trace,
        counters=RECORDED["counters"] if counters is None else counters,
        peaks={"hbm_bytes_per_s": 819e9},
        work_bytes=_work_bytes() if work_bytes is None else work_bytes)


def test_readers_on_the_recorded_run():
    t, c = RECORDED["trace"], RECORDED["counters"]
    stage_bytes = sum(ref.stage_bytes(r) for r in RECORDED["stage_rows"]) \
        / len(RECORDED["stage_rows"])
    stage_s = sum(s for m, s in t["modules_s"].items()
                  if m.startswith("jit_plan_"))
    assert len([m for m in t["modules_s"] if m.startswith("jit_plan_")]) >= 3
    got = {name: load_reader(name)(_run()) for name in READERS}
    assert got == pytest.approx({
        "plan.stage_device_ms_per_query": stage_s / t["queries"] * 1e3,
        "plan.stage_roofline_pct": 100 * (stage_bytes / 819e9)
        / (stage_s / t["queries"]),
        "plan.stage_programs_per_query": c["plan.stage_programs"]
        / c["queries"],
        "plan.cache_misses_per_query": 0.0,
        "plan.distinct_programs": len(t["modules_s"])})
    assert got["plan.stage_programs_per_query"] == 4
    assert 0 < got["plan.stage_roofline_pct"] <= 100


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_stage_programs_gives_nothing(name):
    """The parent: no ``jit_plan_*`` module in the trace, no
    ``plan.stage_programs`` counter; and an untraced run."""
    parent_trace = dict(RECORDED["trace"], modules_s={
        m: s for m, s in RECORDED["trace"]["modules_s"].items()
        if not m.startswith("jit_plan_")})
    parent = _run(trace=parent_trace,
                  counters={"queries": 5, "plan_cache.miss": 3,
                            "host.syncs": 30})
    if name == "plan.distinct_programs":
        assert load_reader(name)(parent) == len(parent_trace["modules_s"])
    else:
        assert load_reader(name)(parent) is None
    untraced = _run(trace={}, counters={"queries": 0})
    assert load_reader(name)(untraced) is None
    # another configuration's work_bytes: plain numbers
    other = _run(work_bytes=[536870912])
    if name == "plan.stage_roofline_pct":
        assert load_reader(name)(other) is None
