"""The two-sweep Pallas segmented scan (ops/pallas_scan.py) must be a
faithful drop-in for the scatters and XLA scans it stands in for on a TPU:
identical segment semantics (restart at boundaries, element-order
rounding) across ops, dtypes, block boundaries, and the end-to-end
groupby that consumes it (a TPU's ``segsum``, ops/realization.py)."""
import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

from cylon_tpu.ops import pallas_scan, realization, segments


@pytest.fixture
def rng():
    return np.random.default_rng(77)


def _golden(x, r, op):
    seg = np.cumsum(r)
    s = pd.Series(x).groupby(seg)
    return {"sum": s.cumsum, "min": s.cummin, "max": s.cummax}[op]().to_numpy()


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segmented_scan_matches_golden(rng, op):
    # sizes straddling the sublane (128) and block (256-lane) boundaries
    for n in (1, 127, 129, 4096, 33000):
        for dt in (np.float32, np.int32, np.uint32):
            x = (rng.random(n) * 50).astype(dt)
            r = rng.random(n) < 0.02
            r[0] = True
            got = np.asarray(pallas_scan.segmented_scan(
                jnp.asarray(x), jnp.asarray(r), op, interpret=True,
                block_lanes=256))
            exp = _golden(x, r, op).astype(dt)
            if dt == np.float32 and op == "sum":
                # float sums round in combine-tree order (contained per
                # segment) — tolerance, not bitwise, vs the sequential golden
                np.testing.assert_allclose(got, exp, rtol=1e-5)
            else:
                np.testing.assert_array_equal(got, exp)


def test_segmented_scan_single_segment_and_all_boundaries(rng):
    n = 5000
    x = rng.random(n).astype(np.float32)
    # one open segment: inclusive prefix
    r = np.zeros(n, bool)
    got = np.asarray(pallas_scan.segmented_scan(
        jnp.asarray(x), jnp.asarray(r), "sum", interpret=True,
        block_lanes=256))
    np.testing.assert_allclose(got, _golden(x, np.r_[True, r[1:]], "sum"),
                               rtol=1e-6)
    # every row its own segment: identity
    r = np.ones(n, bool)
    got = np.asarray(pallas_scan.segmented_scan(
        jnp.asarray(x), jnp.asarray(r), "min", interpret=True,
        block_lanes=256))
    np.testing.assert_array_equal(got, x)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segmented_reduce_sorted_pallas_mode_agrees(rng, op):
    """segments.segmented_reduce_sorted on four-byte rows (the Pallas
    kernel) must agree with the scatter it stands in for,
    ``jax.ops.segment_*`` (to float tolerance for sums: the combine trees
    differ in shape, so f32 sums are not bitwise equal)."""
    import jax

    n = 10000
    x = rng.random(n).astype(np.float32)
    r = rng.random(n) < 0.01
    r[0] = True
    seg = np.cumsum(r) - 1
    groups = int(seg[-1]) + 1
    end = np.searchsorted(seg, np.arange(groups), side="right")
    end_full = np.full(n, 1, np.int32)
    end_full[:len(end)] = end
    got = np.asarray(segments.segmented_reduce_sorted(
        jnp.asarray(x), jnp.asarray(r), jnp.asarray(end_full), op))
    scatter = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}[op]
    exp = np.asarray(scatter(jnp.asarray(x), jnp.asarray(seg), groups))
    np.testing.assert_allclose(got[:groups], exp, rtol=1e-5)


def test_groupby_end_to_end_pallas_segsum(rng, realize):
    """Full pipeline groupby with the Pallas scan backing segment
    reductions — a TPU's ``segsum``, checked here in interpret mode
    against pandas."""
    from cylon_tpu.context import CylonContext
    from cylon_tpu.table import Table

    n = 20000
    df = pd.DataFrame({"k": rng.integers(0, 500, n).astype(np.int64),
                       "v": rng.random(n).astype(np.float64)})
    ctx = CylonContext.Init()
    t = Table.from_pandas(df, ctx=ctx)
    with realize(realization.current()._replace(segsum="pallas")):
        got = (t.groupby("k", {"v": ["sum", "mean", "min", "max"]})
               .to_pandas().sort_values("k").reset_index(drop=True))
    exp = (df.groupby("k").agg(sum_v=("v", "sum"), mean_v=("v", "mean"),
                               min_v=("v", "min"), max_v=("v", "max"))
           .reset_index().sort_values("k").reset_index(drop=True))
    np.testing.assert_array_equal(got["k"].to_numpy(), exp["k"].to_numpy())
    for c, e in (("sum_v", "sum_v"), ("mean_v", "mean_v"),
                 ("min_v", "min_v"), ("max_v", "max_v")):
        np.testing.assert_allclose(got[c].to_numpy(), exp[e].to_numpy(),
                                   rtol=1e-5)


def test_segmented_scan_rejects_wide_dtypes():
    with pytest.raises(ValueError):
        pallas_scan.segmented_scan(jnp.zeros(4, jnp.float64),
                                   jnp.zeros(4, bool), "sum", interpret=True)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("reverse", [False, True])
def test_scan_1d_matches_xla(rng, op, reverse):
    """The unsegmented two-pass scan must match lax.cumsum/cummax/cummin
    exactly for int32 (and to tolerance for f32 sums)."""
    import jax

    for n in (1, 200, 33000):
        x = (rng.random(n) * 1000).astype(np.int32)
        got = np.asarray(pallas_scan.scan_1d(
            jnp.asarray(x), op, reverse=reverse, interpret=True,
            block_lanes=256))
        f = {"sum": jnp.cumsum, "min": jax.lax.cummin,
             "max": jax.lax.cummax}[op]
        exp = np.asarray(f(jnp.asarray(x), reverse=reverse) if op != "sum"
                         else (jnp.flip(jnp.cumsum(jnp.flip(jnp.asarray(x))))
                               if reverse else jnp.cumsum(jnp.asarray(x))))
        np.testing.assert_array_equal(got, exp)


def test_run_extents_pallas_scan_agrees(rng, realize):
    """run_extents through the Pallas scans (a TPU's ``scan``) must agree
    exactly with the XLA scan path (int32 scans are exact in both)."""
    n = 20000
    member = rng.random(n) < 0.5
    # synthetic run structure: starts every ~10 rows, ends before starts
    new_group = rng.random(n) < 0.1
    new_group[0] = True
    is_run_end = np.roll(new_group, -1)
    is_run_end[-1] = True
    args = (jnp.asarray(member), jnp.asarray(new_group),
            jnp.asarray(is_run_end))
    assert segments.plain_scan_mode() == "xla"
    s0, c0 = segments.run_extents(*args)
    with realize(realization.current()._replace(scan="pallas")):
        s1, c1 = segments.run_extents(*args)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))


@pytest.mark.slow
def test_segmented_scan_randomized_soak(rng):
    """Randomized soak across sizes, densities, ops, dtypes, and block
    widths — the confidence bar for ever making the Pallas scans a
    default (mirrors the chunked-engine soak discipline)."""
    import jax

    for case in range(30):
        n = int(rng.integers(1, 200000))
        dens = float(rng.uniform(0.0005, 0.3))
        op = ["sum", "min", "max"][case % 3]
        dt = [np.float32, np.int32, np.uint32][(case // 3) % 3]
        bl = int(rng.choice([128, 256, 1024]))
        x = (rng.random(n) * 100).astype(dt)
        r = rng.random(n) < dens
        if n:
            r[0] = True
        got = np.asarray(pallas_scan.segmented_scan(
            jnp.asarray(x), jnp.asarray(r), op, interpret=True,
            block_lanes=bl))
        exp = _golden(x, r, op).astype(dt)
        if dt == np.float32 and op == "sum":
            np.testing.assert_allclose(got, exp, rtol=1e-4,
                                       err_msg=f"case {case} n={n}")
        else:
            np.testing.assert_array_equal(got, exp, f"case {case} n={n}")
        # plain scan against lax on the same draw
        xi = x.astype(np.int32)
        rev = bool(case % 2)
        got2 = np.asarray(pallas_scan.scan_1d(
            jnp.asarray(xi), op, reverse=rev, interpret=True,
            block_lanes=bl))
        f = {"sum": None, "min": jax.lax.cummin, "max": jax.lax.cummax}[op]
        if op == "sum":
            e = jnp.cumsum(jnp.flip(jnp.asarray(xi))) if rev \
                else jnp.cumsum(jnp.asarray(xi))
            exp2 = np.asarray(jnp.flip(e) if rev else e)
        else:
            exp2 = np.asarray(f(jnp.asarray(xi), reverse=rev))
        np.testing.assert_array_equal(got2, exp2, f"plain case {case} n={n}")
