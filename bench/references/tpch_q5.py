"""Plain reference of TPC-H Q5 (local supplier volume, spec 2.4.5): the six
tables from the seed by the population rules of spec 4.2.3 as far as Q5's
columns go, the answer in pandas float64, the lower-precision control, the
comparison, and the bytes the query and its stages cannot avoid.  Imports
nothing of cylon_tpu or examples/ and takes nothing they made.
"""
from __future__ import annotations

import datetime

import numpy as np
import pandas as pd

from bench.references.common import max_rel_err, round_bf16

# spec 4.2.5: rows at scale factor 1 (lineitem follows from the orders)
ORDERS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 150_000
SUPPLIERS_PER_SF = 10_000
PARTS_PER_SF = 200_000

# spec 4.2.3: the 25 nations with their regions, the five regions
NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
           "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
           "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
           "UNITED KINGDOM", "UNITED STATES"]
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                 4, 2, 3, 3, 1]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# spec 2.4.5.3: DATE is 1 January of a year in [1993, 1997]
YEARS = [1993, 1994, 1995, 1996, 1997]
# dates are day ordinals from STARTDATE; o_orderdate is uniform over
# [STARTDATE, ENDDATE - 151 days] = 1992-01-01 ... 1998-08-02
_EPOCH = datetime.date(1992, 1, 1)
ORDERDATE_DAYS = (datetime.date(1998, 8, 2) - _EPOCH).days + 1
NAME_BYTES = 14  # the longest n_name; r_name's longest is 11


def rows_at(sf: float) -> dict:
    return {"customer": int(CUSTOMERS_PER_SF * sf),
            "orders": int(ORDERS_PER_SF * sf),
            "supplier": int(SUPPLIERS_PER_SF * sf),
            "part": int(PARTS_PER_SF * sf)}


def make_data(cfg: dict, chips: int, seed: int) -> dict:
    """Spec 4.2.3, Q5's columns.  Keys start at 1.  Orders: sparse keys
    (the first 8 of every 32), a customer whose key is no multiple of 3,
    a date uniform over the order range, 1 to 7 lines.  Lines: a part
    uniform over the parts, one of its four suppliers by the spec's
    formula, quantity 1..50 times the part's retail price (the spec's
    function of the part key), discount 0.00..0.10."""
    n = rows_at(float(cfg["scale_factor"]))
    rng = np.random.default_rng(seed)
    i32 = np.int32
    nations = len(NATIONS)
    seq = np.arange(n["orders"], dtype=np.int64)
    o_orderkey = ((seq >> 3 << 5) + (seq & 7) + 1).astype(i32)
    # custkey: uniform over the keys in [1, customers] that 3 does not divide
    live = n["customer"] - n["customer"] // 3
    j = rng.integers(0, live, n["orders"])
    o_custkey = (j + j // 2 + 1).astype(i32)
    lines = rng.integers(1, 8, n["orders"], dtype=i32)
    n_l = int(lines.sum())
    partkey = rng.integers(1, n["part"] + 1, n_l, dtype=i32)
    corner = rng.integers(0, 4, n_l, dtype=i32)
    s = n["supplier"]
    l_suppkey = (partkey + corner * (s // 4 + (partkey - 1) // s)) % s + 1
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    quantity = rng.integers(1, 51, n_l, dtype=i32)
    return {
        "customer": {
            "c_custkey": np.arange(1, n["customer"] + 1, dtype=i32),
            "c_nationkey": rng.integers(0, nations, n["customer"]).astype(i32)},
        "orders": {
            "o_orderkey": o_orderkey,
            "o_custkey": o_custkey,
            "o_orderdate": rng.integers(0, ORDERDATE_DAYS,
                                        n["orders"]).astype(i32)},
        "lineitem": {
            "l_orderkey": np.repeat(o_orderkey, lines),
            "l_suppkey": l_suppkey,
            "l_extendedprice": (quantity * retail_cents) / 100.0,
            "l_discount": rng.integers(0, 11, n_l, dtype=i32) / 100.0},
        "supplier": {
            "s_suppkey": np.arange(1, s + 1, dtype=i32),
            "s_nationkey": rng.integers(0, nations, s).astype(i32)},
        "nation": {
            "n_nationkey": np.arange(nations, dtype=i32),
            "n_regionkey": np.asarray(NATION_REGION, i32),
            "n_name": np.array(NATIONS, object)},
        "region": {
            "r_regionkey": np.arange(len(REGIONS), dtype=i32),
            "r_name": np.array(REGIONS, object)},
    }


def queries(cfg: dict, seed: int) -> list:
    """Five substitutions: every region once and every year once, paired
    and ordered by the seed, so no two consecutive queries are the same
    and every seed issues the same set of sizes."""
    rng = np.random.default_rng([seed, 5])
    out = []
    for r, y in zip(rng.permutation(len(REGIONS)), rng.permutation(YEARS)):
        y = int(y)
        out.append({"region": REGIONS[r], "region_key": int(r), "year": y,
                    "date_lo": (datetime.date(y, 1, 1) - _EPOCH).days,
                    "date_hi": (datetime.date(y + 1, 1, 1) - _EPOCH).days})
    return out


def _rows(table: dict) -> int:
    return len(next(iter(table.values())))


def input_rows(data: dict, query: dict) -> int:
    """The rows of all six tables: what one query is asked over."""
    return sum(_rows(t) for t in data.values())


def answer(data: dict, query: dict, precision: str = "f64") -> dict:
    """Revenue by nation of the region's local suppliers in the year,
    revenue descending.  The query's own order of joins and filters, but
    for one step: lineitem is restricted to the year's orders before it is
    merged (a semi-join that changes no row of the result).
    ``precision="bf16"`` is the control: every value and every result of
    arithmetic rounded to bfloat16."""
    if precision not in ("f64", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    rnd = round_bf16 if precision == "bf16" else (lambda x: x)
    c, o, s, n, r = (pd.DataFrame(data[t]) for t in (
        "customer", "orders", "supplier", "nation", "region"))
    o_year = o[(o.o_orderdate >= query["date_lo"])
               & (o.o_orderdate < query["date_hi"])]
    wanted = np.zeros(int(o.o_orderkey.max()) + 1, bool)
    wanted[o_year.o_orderkey.to_numpy()] = True
    line = data["lineitem"]
    pick = wanted[line["l_orderkey"]]
    l = pd.DataFrame({name: col[pick] for name, col in line.items()})
    j = (c.merge(o_year, left_on="c_custkey", right_on="o_custkey")
         .merge(l, left_on="o_orderkey", right_on="l_orderkey")
         .merge(s, left_on="l_suppkey", right_on="s_suppkey"))
    stage_rows = {"orders": len(o), "orders_in_year": len(o_year),
                  "lines_joined": len(j)}
    j = j[j.c_nationkey == j.s_nationkey]
    stage_rows["lines_local"] = len(j)
    j = (j.merge(n, left_on="c_nationkey", right_on="n_nationkey")
         .merge(r, left_on="n_regionkey", right_on="r_regionkey"))
    j = j[j.r_name == query["region"]]
    stage_rows["lines_in_region"] = len(j)
    price = rnd(j.l_extendedprice.to_numpy(np.float64))
    disc = rnd(j.l_discount.to_numpy(np.float64))
    j = j.assign(revenue=rnd(price * rnd(1.0 - disc)))
    g = j.groupby("n_name").revenue.sum().reset_index()
    g["revenue"] = rnd(g.revenue.to_numpy())
    g = g.sort_values(["revenue", "n_name"], ascending=[False, True])
    return {"n_name": g.n_name.to_numpy().astype(str),
            "sum_revenue": g.revenue.to_numpy(np.float64),
            "stage_rows": stage_rows}


def compare(got: dict, exp: dict) -> dict:
    """Nations exact; each nation's revenue against the reference's; the
    program's own order (revenue descending, name breaking ties) exact on
    its own numbers, so the order is right as far as the revenues are."""
    names = np.asarray(got["n_name"]).astype(str)
    rev = np.asarray(got["sum_revenue"], np.float64)
    by_name = dict(zip(exp["n_name"], exp["sum_revenue"]))
    both = [i for i, nm in enumerate(names) if nm in by_name]
    out_of_order = sum(
        1 for i in range(len(rev) - 1)
        if (rev[i], names[i + 1]) < (rev[i + 1], names[i]))
    return {
        "nations_wrong": len(set(names) ^ set(by_name))
        + (len(names) - len(set(names))),
        "order_wrong": out_of_order,
        "revenue_rel_err": max_rel_err(
            rev[both], [by_name[names[i]] for i in both]),
    }


class QueryBytes(int):
    """``work_bytes``' number with ``stage_bytes`` beside it: of all the
    reference answers, the harness keeps each query's ``work_bytes`` for
    the metrics' readers (``run.work_bytes``) and nothing else."""

    stage_bytes = 0


def work_bytes(data: dict, query: dict, exp: dict) -> QueryBytes:
    """Bytes the query cannot avoid: every resident column once (names at
    their longest), and the result written once."""
    read = sum(_rows(t) * sum(NAME_BYTES if col.dtype == object
                              else col.itemsize for col in t.values())
               for t in data.values())
    out = QueryBytes(read + len(exp["n_name"]) * (NAME_BYTES + 8))
    out.stage_bytes = stage_bytes(exp["stage_rows"])
    return out


def stage_bytes(stage_rows: dict) -> int:
    """Bytes Q5's two filters with their compactions, its key comparison
    and its revenue expression have to read and write, from the rows the
    reference counted in and out of each logical stage, at the widths of
    the columns a stage needs of its input and the rest of the query needs
    of its output: it reads the same whatever implements the stages."""
    key, date, money = 4, 4, 8
    return (
        # the date range: orderkey, custkey, date in; orderkey, custkey out
        stage_rows["orders"] * (2 * key + date)
        + stage_rows["orders_in_year"] * 2 * key
        # c_nationkey = s_nationkey: both keys, price, discount in; one out
        + stage_rows["lines_joined"] * (2 * key + 2 * money)
        + stage_rows["lines_local"] * (key + 2 * money)
        # the region: its key, the name, price, discount in; all but the key out
        + stage_rows["lines_local"] * (key + NAME_BYTES + 2 * money)
        + stage_rows["lines_in_region"] * (NAME_BYTES + 2 * money)
        # the revenue: price and discount in, revenue out
        + stage_rows["lines_in_region"] * 3 * money)
