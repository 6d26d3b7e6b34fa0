"""Plain reference of the join -> group-by -> sort query: data from the
seed, the answer in pandas/NumPy float64, the lower-precision control and
the comparison.  Imports nothing of cylon_tpu and takes nothing it made.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

from bench.references.common import max_rel_err, round_bf16, wrong_count

# bytes of one input row, either side: (k int32, a f32) or (k int32, b f32)
_ROW_BYTES = 8
# result bytes it has to write once: l_k int32, sum_a f32, mean_a f32,
# count_a int32 -- the narrowest types that hold the answer
_GROUP_BYTES = 16


def rows_per_side(cfg: dict, chips: int) -> int:
    return int(cfg["rows_per_side_by_chips"][str(chips)])


def make_data(cfg: dict, chips: int, seed: int) -> dict:
    """Keys are one uniform sample of [0, rows), drawn from the
    configuration's ``shape_seed`` with chip_smoke.make_data's recipe: the
    multiset of keys, so every join, group and shard size, is the same in
    every run.  ``seed`` draws the order of the rows and all the values, so
    the same seed gives the same inputs and two seeds the same work in
    another order."""
    rows = rows_per_side(cfg, chips)
    shape = np.random.default_rng(int(cfg["shape_seed"]))
    lk = shape.integers(0, rows, rows).astype(np.int32)
    shape.random(rows)  # the recipe's draw order: lk, lv, rk, rv
    rk = shape.integers(0, rows, rows).astype(np.int32)
    rng = np.random.default_rng(seed)
    return {"left": {"k": lk[rng.permutation(rows)],
                     "a": rng.random(rows, np.float32)},
            "right": {"k": rk[rng.permutation(rows)],
                      "b": rng.random(rows, np.float32)}}


def queries(cfg: dict, seed: int) -> list:
    """One query, issued back to back."""
    return [{}]


def input_rows(data: dict, query: dict) -> int:
    return len(data["left"]["k"]) + len(data["right"]["k"])


def answer(data: dict, query: dict, precision: str = "f64") -> dict:
    """merge on k, group by k with sum/mean/count of a, order by
    (count desc, k asc).  ``precision="bf16"`` is the control: every
    value and every result of arithmetic rounded to bfloat16."""
    left = pd.DataFrame(data["left"])
    right = pd.DataFrame(data["right"])
    left["a"] = left["a"].astype(np.float64)
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
    """Bytes the query cannot avoid: each input column read once and the
    result written once, whatever implements it."""
    return (input_rows(data, query) * _ROW_BYTES
            + len(exp["l_k"]) * _GROUP_BYTES)
