"""Plain reference of TPC-H Q5 (local supplier volume): the six tables
from the seed (a copy of examples/tpch_data.py's recipe, Q5's columns
only), the answer in pandas float64, the lower-precision control and the
comparison.  Imports nothing of cylon_tpu and takes nothing it made.
"""
from __future__ import annotations

import datetime

import numpy as np
import pandas as pd

from bench.references.common import max_rel_err, round_bf16

LINEITEM_ROWS_PER_SF = 6_000_000
ORDERS_ROWS_PER_SF = 1_500_000
CUSTOMER_ROWS_PER_SF = 150_000
SUPPLIER_ROWS_PER_SF = 10_000

NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
           "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
           "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
           "UNITED KINGDOM", "UNITED STATES"]
# nation -> region (nationkey order), the spec's five regions
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                 4, 2, 3, 3, 1]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# spec 2.4.5.3: DATE is 1 January of a year in [1993, 1997]
YEARS = [1993, 1994, 1995, 1996, 1997]
_EPOCH = datetime.date(1992, 1, 1)
# order dates are day ordinals from 1992-01-01 over about seven years
DATE_LO, DATE_HI = 0, 2556


def make_data(cfg: dict, chips: int, seed: int) -> dict:
    sf = float(cfg["scale_factor"])
    rng = np.random.default_rng(seed)
    n_c = int(CUSTOMER_ROWS_PER_SF * sf)
    n_o = int(ORDERS_ROWS_PER_SF * sf)
    n_l = int(LINEITEM_ROWS_PER_SF * sf)
    n_s = int(SUPPLIER_ROWS_PER_SF * sf)
    i32 = np.int32
    return {
        "customer": {
            "c_custkey": np.arange(n_c, dtype=i32),
            "c_nationkey": rng.integers(0, len(NATIONS), n_c).astype(i32)},
        "orders": {
            "o_orderkey": np.arange(n_o, dtype=i32),
            "o_custkey": rng.integers(0, n_c, n_o).astype(i32),
            "o_orderdate": rng.integers(DATE_LO, DATE_HI, n_o).astype(i32)},
        "lineitem": {
            "l_orderkey": rng.integers(0, n_o, n_l).astype(i32),
            "l_suppkey": rng.integers(0, n_s, n_l).astype(i32),
            "l_extendedprice": rng.random(n_l, np.float32) * 90000 + 900,
            "l_discount": rng.integers(0, 11, n_l).astype(np.float32) / 100},
        "supplier": {
            "s_suppkey": np.arange(n_s, dtype=i32),
            "s_nationkey": rng.integers(0, len(NATIONS), n_s).astype(i32)},
        "nation": {
            "n_nationkey": np.arange(len(NATIONS), dtype=i32),
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


def input_rows(data: dict, query: dict) -> int:
    return sum(len(next(iter(data[t].values())))
               for t in ("lineitem", "orders", "customer"))


def answer(data: dict, query: dict, precision: str = "f64") -> dict:
    """Revenue by nation of the region's local suppliers in the year,
    revenue descending.  ``precision="bf16"`` is the control: every value
    and every result of arithmetic rounded to bfloat16."""
    if precision not in ("f64", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")
    rnd = round_bf16 if precision == "bf16" else (lambda x: x)
    c, o, l, s, n, r = (pd.DataFrame(data[t]) for t in (
        "customer", "orders", "lineitem", "supplier", "nation", "region"))
    o = o[(o.o_orderdate >= query["date_lo"])
          & (o.o_orderdate < query["date_hi"])]
    j = (c.merge(o, left_on="c_custkey", right_on="o_custkey")
         .merge(l, left_on="o_orderkey", right_on="l_orderkey")
         .merge(s, left_on="l_suppkey", right_on="s_suppkey"))
    j = j[j.c_nationkey == j.s_nationkey]
    j = (j.merge(n, left_on="c_nationkey", right_on="n_nationkey")
         .merge(r, left_on="n_regionkey", right_on="r_regionkey"))
    j = j[j.r_name == query["region"]]
    price = rnd(j.l_extendedprice.to_numpy(np.float64))
    disc = rnd(j.l_discount.to_numpy(np.float64))
    j = j.assign(revenue=rnd(price * rnd(1.0 - disc)))
    g = j.groupby("n_name").revenue.sum().reset_index()
    g["revenue"] = rnd(g.revenue.to_numpy())
    g = g.sort_values(["revenue", "n_name"], ascending=[False, True])
    return {"n_name": g.n_name.to_numpy().astype(str),
            "sum_revenue": g.revenue.to_numpy(np.float64)}


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


def work_bytes(data: dict, query: dict, exp: dict) -> int:
    """Bytes the query cannot avoid: every column Q5 reads, once (names
    at their longest, 14 bytes), and the result written once."""
    rows = {t: len(next(iter(cols.values()))) for t, cols in data.items()}
    read = (rows["customer"] * 8 + rows["orders"] * 12
            + rows["lineitem"] * 16 + rows["supplier"] * 8
            + rows["nation"] * (8 + 14) + rows["region"] * (4 + 11))
    return read + len(exp["n_name"]) * (14 + 4)
