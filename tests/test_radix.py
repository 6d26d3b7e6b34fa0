"""Radix fast-path sort (ops/radix.py) vs the lax.sort comparison path.

The radix sort must produce BIT-IDENTICAL results to the cmp path for
every packed fast-path shape: both resolve ties by the embedded row
index, so (perm, sorted operands) — not just the sorted keys — must
agree exactly.  Replaces measurement-free trust in the new sort before
the TPU battery A/Bs its speed (reference hot loops being attacked:
join/join.cpp:78-257, util/sort.hpp).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cylon_tpu import column as colmod
from cylon_tpu.ops import keys, radix


def _operands_int(vals: np.ndarray, count: int, capacity: int):
    col = colmod.from_numpy(vals, capacity=capacity)
    return keys.build_operands([col], jnp.asarray(count, jnp.int32), capacity)


def _ab(operands, capacity, monkeypatch, bits="1", scan=None):
    monkeypatch.delenv("CYLON_TPU_SORT", raising=False)
    perm_cmp, ops_cmp, _ = keys.lexsort_indices(operands, capacity)
    monkeypatch.setenv("CYLON_TPU_SORT", "radix")
    monkeypatch.setenv("CYLON_TPU_RADIX_BITS", bits)
    if scan is not None:
        monkeypatch.setenv("CYLON_TPU_RADIX_SCAN", scan)
    perm_rad, ops_rad, _ = keys.lexsort_indices(operands, capacity)
    np.testing.assert_array_equal(np.asarray(perm_cmp), np.asarray(perm_rad))
    assert len(ops_cmp) == len(ops_rad)
    for a, b in zip(ops_cmp, ops_rad):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return perm_rad


@pytest.mark.parametrize("bits", ["1", "2", "4"])
def test_radix_matches_cmp_64bit_branch(monkeypatch, bits):
    # padding(1) + validity(1) + i32 key(32) + idx -> the 64-bit branch
    rng = np.random.default_rng(7)
    cap, count = 1 << 12, (1 << 12) - 37
    vals = rng.integers(-(1 << 30), 1 << 30, cap).astype(np.int32)
    ops = _operands_int(vals, count, cap)
    assert sum(keys._ordered_unsigned(o)[1] for o in ops) + 12 > 32
    _ab(ops, cap, monkeypatch, bits=bits)


@pytest.mark.parametrize("scan", ["matmul", "xla"])
def test_radix_matches_cmp_32bit_branch(monkeypatch, scan):
    # padding(1) + validity(1) + u8 key(8) + idx(≤22) -> single-word branch
    rng = np.random.default_rng(8)
    cap, count = 1 << 10, 900
    vals = rng.integers(0, 256, cap).astype(np.uint8)
    ops = _operands_int(vals, count, cap)
    total = sum(keys._ordered_unsigned(o)[1] for o in ops)
    assert total + 10 <= 32
    _ab(ops, cap, monkeypatch,
        scan=(None if scan == "matmul" else "xla"))


def test_radix_stability_ties(monkeypatch):
    # heavy duplicates: tie-break must equal the embedded-index order
    rng = np.random.default_rng(9)
    cap, count = 1 << 11, (1 << 11) - 5
    vals = rng.integers(0, 7, cap).astype(np.int32)
    ops = _operands_int(vals, count, cap)
    perm = _ab(ops, cap, monkeypatch)
    p = np.asarray(perm)[:count]
    v = np.asarray(vals)[p]
    assert (np.diff(v) >= 0).all()
    for val in range(7):
        idx = p[v == val]
        assert (np.diff(idx) > 0).all()  # stable within equal keys


def test_radix_floats_negatives_nans(monkeypatch):
    rng = np.random.default_rng(10)
    cap = 1 << 10
    vals = rng.standard_normal(cap).astype(np.float32)
    vals[::17] = np.nan
    vals[::13] = -0.0
    ops = _operands_int(vals, cap, cap)
    _ab(ops, cap, monkeypatch)


def test_radix_nonblock_sizes(monkeypatch):
    # capacity not a multiple of the matmul-scan block: fallback cumsum
    rng = np.random.default_rng(11)
    for cap in (8, 100, 257, 1000):
        vals = rng.integers(0, 50, cap).astype(np.int32)
        ops = _operands_int(vals, cap, cap)
        _ab(ops, cap, monkeypatch)


def test_cumsum_matmul_matches_xla():
    rng = np.random.default_rng(12)
    m = jnp.asarray(rng.integers(0, 2, 1 << 14).astype(bool))
    got = radix._cumsum_i32(m)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.cumsum(np.asarray(m)).astype(np.int32))


@pytest.mark.slow
def test_join_level_radix_agreement(monkeypatch):
    """End-to-end: join + groupby pipeline results agree across sort modes.
    jit caches key on shapes only (env is read at trace time), so caches
    are cleared between modes."""
    from cylon_tpu.config import JoinType
    from cylon_tpu.ops import join as join_mod

    rng = np.random.default_rng(13)
    cap = 1 << 10
    lk = rng.integers(0, 300, cap).astype(np.int32)
    rk = rng.integers(0, 300, cap).astype(np.int32)
    cols_l = (colmod.from_numpy(lk),)
    cols_r = (colmod.from_numpy(rk),)
    count = jnp.asarray(cap, jnp.int32)

    results = {}
    for mode in ("cmp", "radix"):
        monkeypatch.setenv("CYLON_TPU_SORT", mode)
        jax.clear_caches()
        m = int(join_mod.join_row_count(cols_l, count, cols_r, count,
                                        (0,), (0,), JoinType.INNER, "sort"))
        out, n = join_mod.join_gather(cols_l, count, cols_r, count,
                                      (0,), (0,), JoinType.INNER,
                                      1 << 14, "sort")
        results[mode] = (m, int(n), np.sort(np.asarray(out[0].data)[:m]))
    monkeypatch.delenv("CYLON_TPU_SORT", raising=False)
    jax.clear_caches()
    assert results["cmp"][0] == results["radix"][0]
    assert results["cmp"][1] == results["radix"][1]
    np.testing.assert_array_equal(results["cmp"][2], results["radix"][2])
