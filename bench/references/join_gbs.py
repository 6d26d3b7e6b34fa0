"""Plain reference of the join -> group-by -> sort query: data from the
seed, the answer in pandas/NumPy float64, the lower-precision control and
the comparison.  Imports nothing of cylon_tpu and takes nothing it made.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

from bench.references.common import max_rel_err, round_bf16, wrong_count



def rows_per_side(cfg: dict, chips: int) -> int:
    return int(cfg["rows_per_side_by_chips"][str(chips)])


def make_data(cfg: dict, chips: int, seed: int) -> dict:
    """Two tables of the configuration's column types, every key and every
    value drawn from ``seed`` (chip_smoke.make_data's recipe and draw
    order: left keys, left values, right keys, right values).  Keys are
    uniform in [0, rows), so an inner join matches about 1:1."""
    rows = rows_per_side(cfg, chips)
    rng = np.random.default_rng(seed)
    out = {}
    for side, value in (("left", "a"), ("right", "b")):
        types = cfg["tables"][side]
        out[side] = {
            "k": rng.integers(0, rows, rows).astype(types["k"], copy=False),
            value: rng.random(rows).astype(types[value], copy=False)}
    return out


def queries(cfg: dict, seed: int) -> list:
    """One query, issued back to back."""
    return [{}]


def input_rows(data: dict, query: dict) -> int:
    return len(data["left"]["k"]) + len(data["right"]["k"])


def answer(data: dict, query: dict, precision: str = "f64") -> dict:
    """merge on k, group by k with sum/mean/count of a, order by
    (count desc, k asc).  ``precision="bf16"`` is the control: every
    value and every result of arithmetic rounded to bfloat16."""
    left = pd.DataFrame(data["left"]).astype({"a": np.float64})
    right = pd.DataFrame(data["right"])
    if precision == "bf16":
        left["a"] = round_bf16(left["a"].to_numpy())
    elif precision != "f64":
        raise ValueError(f"unknown precision {precision!r}")
    merged = left.merge(right[["k"]], on="k")
    gb = merged.groupby("k")["a"].agg(["sum", "mean", "count"]).reset_index()
    gb = gb.sort_values(["count", "k"], ascending=[False, True])
    out = {"l_k": gb["k"].to_numpy(), "sum_a": gb["sum"].to_numpy(),
           "mean_a": gb["mean"].to_numpy(),
           "count_a": gb["count"].to_numpy(), "join_rows": len(merged)}
    if precision == "bf16":
        out["sum_a"] = round_bf16(out["sum_a"])
        out["mean_a"] = round_bf16(out["mean_a"])
    return out


def compare(got: dict, exp: dict) -> dict:
    """The numbers that decide ``correct``.  The order is integer and
    total, so keys and counts are compared position by position."""
    return {
        "groups_off": abs(len(got["l_k"]) - len(exp["l_k"])),
        "join_rows_off": abs(int(np.sum(got["count_a"]))
                             - int(exp["join_rows"])),
        "keys_wrong": wrong_count(got["l_k"], exp["l_k"]),
        "counts_wrong": wrong_count(got["count_a"], exp["count_a"]),
        "sum_rel_err": max_rel_err(got["sum_a"], exp["sum_a"]),
        "mean_rel_err": max_rel_err(got["mean_a"], exp["mean_a"]),
    }


def work_bytes(data: dict, query: dict, exp: dict) -> int:
    """Bytes the query cannot avoid, whatever implements it: each input
    column read once, and the result written once as a key and a count of
    the key's type and a sum and a mean of the value's."""
    read = sum(col.nbytes for side in data.values() for col in side.values())
    key, value = data["left"]["k"].itemsize, data["left"]["a"].itemsize
    return read + len(exp["l_k"]) * 2 * (key + value)
