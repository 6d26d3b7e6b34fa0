"""Plain reference of the join -> group-by -> sort query on a foreign-key
join under skew: the fact (left) table's keys are drawn from a Zipf
distribution over the dimension (right) table's, which holds every key
once.  The data is made here; the answer, its lower-precision control, the
rows and the bytes are ``join_gbs``'s, and the comparison is ``join_gbs``'s
with the hottest key's group read on its own.  Imports nothing of
cylon_tpu and takes nothing it made.
"""
from __future__ import annotations

import numpy as np

from bench.references import join_gbs
from bench.references.common import max_rel_err
from bench.references.join_gbs import (  # noqa: F401  (the harness's names)
    answer, input_rows, queries, rows_per_side, work_bytes)


def zipf_ranks(rng, n: int, s: float, size: int) -> np.ndarray:
    """``size`` ranks in [1, n], P(rank = r) proportional to r**-s, by
    inverse CDF: one uniform draw a rank, looked up in the cumulative
    weights (float64: the smallest step at n = 16,000,000 and s = 1.25
    is 2e-10, far above the rounding of a sum near 1)."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -s)
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(size), side="right") + 1


def make_data(cfg: dict, chips: int, seed: int) -> dict:
    """Two tables of the configuration's column types from ``seed`` alone,
    drawn in ``join_gbs.make_data``'s order: left keys, left values, right
    keys, right values.  Left ``k`` = rank - 1 with the rank Zipf
    (``cfg["zipf_s"]``) over 1 ... rows, so key 0 is the hottest whatever
    the seed; right ``k`` = a permutation of [0, rows): every left row
    matches exactly one right row, and the join has ``rows`` rows."""
    rows = rows_per_side(cfg, chips)
    rng = np.random.default_rng(seed)
    left, right = cfg["tables"]["left"], cfg["tables"]["right"]
    out = {"left": {}, "right": {}}
    out["left"]["k"] = (zipf_ranks(rng, rows, float(cfg["zipf_s"]), rows)
                        - 1).astype(left["k"], copy=False)
    out["left"]["a"] = rng.random(rows).astype(left["a"], copy=False)
    out["right"]["k"] = rng.permutation(rows).astype(right["k"], copy=False)
    out["right"]["b"] = rng.random(rows).astype(right["b"], copy=False)
    return out


def compare(got: dict, exp: dict) -> dict:
    """``join_gbs.compare``'s six numbers, and the sum and the mean of
    the answer's first row by themselves: the order is count descending,
    so that row is the hottest key's group, the one long run the
    accumulation has to carry (a fifth of the rows at s = 1.25), which
    the widest error over half a million short groups need not show."""
    out = join_gbs.compare(got, exp)
    out["hot_sum_rel_err"] = max_rel_err(got["sum_a"][:1], exp["sum_a"][:1])
    out["hot_mean_rel_err"] = max_rel_err(got["mean_a"][:1],
                                          exp["mean_a"][:1])
    return out
