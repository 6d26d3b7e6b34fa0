"""Arithmetic shared by the plain references' comparisons."""
from __future__ import annotations

import ml_dtypes
import numpy as np


def round_bf16(x) -> np.ndarray:
    """float64 values of ``x`` rounded to the nearest bfloat16."""
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def wrong_count(got, exp) -> int:
    """Positions at which two sequences differ; a length mismatch counts
    every missing or extra position."""
    got, exp = np.asarray(got), np.asarray(exp)
    n = min(len(got), len(exp))
    return int(np.sum(got[:n] != exp[:n])) + abs(len(got) - len(exp))


def max_rel_err(got, exp) -> float:
    """Widest |got - exp| / |exp| over positions both have; NaN where the
    program answered NaN counts as infinitely wrong."""
    got = np.asarray(got, np.float64)
    exp = np.asarray(exp, np.float64)
    n = min(len(got), len(exp))
    if n == 0:
        return 0.0
    err = np.abs(got[:n] - exp[:n]) / np.maximum(np.abs(exp[:n]),
                                                 np.finfo(np.float64).tiny)
    return float(np.max(np.where(np.isnan(err), np.inf, err)))
