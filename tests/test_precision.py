"""Narrow (32-bit) accumulation mode: the TPU-fit precision policy.

Wide mode is covered by every other test (CPU default).  Here the same
pipelines run under ``narrow`` and must stay correct within f32 tolerance,
with no f64 tensors in the jaxprs of the core kernels.
"""
import numpy as np
import pandas as pd
import pytest

from cylon_tpu import precision


@pytest.fixture()
def narrow_mode():
    precision.set_accumulation("narrow")
    yield
    precision.set_accumulation(None)


def _table(ctx, df):
    from cylon_tpu.table import Table

    return Table.from_pandas(df, ctx=ctx)


def test_mode_resolution():
    assert precision.accumulation_mode() == "wide"  # cpu default
    precision.set_accumulation("narrow")
    try:
        assert precision.narrow()
        import jax.numpy as jnp
        assert precision.float_acc() == jnp.float32
        assert precision.float_acc_for(jnp.float64) == jnp.float32
        assert precision.int_acc() == jnp.int64
    finally:
        precision.set_accumulation(None)
    with pytest.raises(ValueError):
        precision.set_accumulation("huge")


def test_narrow_groupby_matches_pandas(ctx4, rng, narrow_mode):
    n = 4000
    df = pd.DataFrame({
        "k": rng.integers(0, 50, n),
        "v": rng.random(n).astype(np.float32),
        "w": rng.integers(0, 1000, n).astype(np.int64),
    })
    t = _table(ctx4, df)
    g = t.groupby("k", {"v": ["sum", "mean", "std"], "w": ["sum", "count"]})
    got = g.to_pandas().sort_values("k").reset_index(drop=True)
    exp = df.groupby("k").agg(
        sum_v=("v", "sum"), mean_v=("v", "mean"), std_v=("v", "std"),
        sum_w=("w", "sum"), count_w=("w", "count")).reset_index()
    assert len(got) == len(exp)
    np.testing.assert_allclose(got["sum_v"], exp["sum_v"], rtol=1e-4)
    np.testing.assert_allclose(got["mean_v"], exp["mean_v"], rtol=1e-4)
    # ddof: reference VAR uses ddof=0 by default in our API; pandas std is
    # ddof=1 — compare via the table API's own ddof
    assert np.array_equal(got["sum_w"], exp["sum_w"])  # int64 exact
    assert np.array_equal(got["count_w"], exp["count_w"])
    # narrow mode outputs: f32 stats; counts are i32 partials combined by
    # an integer SUM, which always widens to i64 for overflow safety
    import cylon_tpu.dtypes as dt
    by_name = dict(zip(g.names, g.columns))
    assert by_name["mean_v"].dtype.type == dt.Type.FLOAT
    assert by_name["count_w"].dtype.type == dt.Type.INT64
    assert by_name["sum_w"].dtype.type == dt.Type.INT64


def test_narrow_groupby_jaxpr_is_64bit_free(rng, narrow_mode):
    """An f32/i32 pipeline in narrow mode must trace with zero 64-bit
    tensors — the TPU compile/perf guarantee this mode exists for."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu import column as colmod
    from cylon_tpu.ops import groupby as gmod

    k = colmod.from_numpy(rng.integers(0, 9, 2048).astype(np.int32))
    v = colmod.from_numpy(rng.random(2048).astype(np.float32))
    jaxpr = jax.make_jaxpr(
        lambda cols, n: gmod.hash_groupby(
            cols, n, (0,), ((1, gmod.AggOp.SUM), (1, gmod.AggOp.MEAN),
                            (1, gmod.AggOp.VAR), (0, gmod.AggOp.COUNT)), 0)
    )((k, v), jnp.asarray(2048, jnp.int32))
    import re
    s = str(jaxpr)
    # scalar weak-typed literals (0:i64[]) are free; 64-bit *arrays* are
    # the emulated-scatter/compile liability
    wide_arrays = re.findall(r"[iuf]64\[\d[^\]]*\]", s)
    assert not wide_arrays, f"64-bit arrays in narrow-mode groupby: {wide_arrays[:5]}"


def test_narrow_bench_pipeline_jaxpr_is_64bit_free(rng, narrow_mode):
    """The TPU bench shape (key_grouped join + pipeline groupby) must trace
    64-bit-free in narrow mode — the compile/perf guarantee bench.py relies
    on."""
    import re

    import jax
    import jax.numpy as jnp

    from cylon_tpu import column as colmod
    from cylon_tpu.config import JoinType
    from cylon_tpu.ops import groupby as gmod
    from cylon_tpu.ops import join as jmod

    n = 1024
    k = colmod.from_numpy(rng.integers(0, 200, n).astype(np.int32))
    v = colmod.from_numpy(rng.random(n).astype(np.float32))

    def pipeline(cl, c1, cr, c2):
        joined, jm = jmod.join_gather(cl, c1, cr, c2, (0,), (0,),
                                      JoinType.INNER, 4 * n, "sort",
                                      key_grouped=True)
        gcols, g = gmod.pipeline_groupby(
            joined, jm, (0,), ((1, gmod.AggOp.SUM), (3, gmod.AggOp.MEAN)), 0)
        return gcols[1].data, gcols[2].data, g

    jaxpr = jax.make_jaxpr(pipeline)((k, v), jnp.asarray(n, jnp.int32),
                                     (k, v), jnp.asarray(n, jnp.int32))
    wide = re.findall(r"[iuf]64\[\d[^\]]*\]", str(jaxpr))
    assert not wide, f"64-bit arrays in narrow bench pipeline: {wide[:5]}"


def test_narrow_distributed_sort(ctx4, rng, narrow_mode):
    n = 3000
    df = pd.DataFrame({"a": rng.random(n), "b": rng.integers(0, 9, n)})
    t = _table(ctx4, df)
    s = t.distributed_sort("a")
    vals = s.to_pandas()["a"].to_numpy()
    assert len(vals) == n and np.all(np.diff(vals) >= 0)


def test_narrow_scalar_aggs(ctx2, rng, narrow_mode):
    n = 2048
    df = pd.DataFrame({"x": rng.random(n).astype(np.float32)})
    t = _table(ctx2, df)
    assert abs(float(t.sum("x")) - df["x"].sum()) < 1e-2
    assert int(t.count("x")) == n
    assert abs(float(t.min("x")) - df["x"].min()) < 1e-7
    assert abs(float(t.max("x")) - df["x"].max()) < 1e-7


def test_narrow_join_groupby_pipeline(ctx4, rng, narrow_mode):
    n = 3000
    left = pd.DataFrame({"k": rng.integers(0, 200, n),
                         "a": rng.random(n).astype(np.float32)})
    right = pd.DataFrame({"k": rng.integers(0, 200, n),
                          "b": rng.random(n).astype(np.float32)})
    tl, tr = _table(ctx4, left), _table(ctx4, right)
    j = tl.distributed_join(tr, on="k", how="inner")
    g = j.groupby(j.names[0], {j.names[1]: ["sum"]})
    got = g.to_pandas()
    exp = (left.merge(right, on="k").groupby("k")
           .agg(s=("a", "sum")).reset_index())
    got = got.sort_values(got.columns[0]).reset_index(drop=True)
    assert len(got) == len(exp)
    np.testing.assert_allclose(got[got.columns[1]], exp["s"], rtol=1e-3)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.float64],
                         ids=["i8", "i16", "f64"])
def test_prefix_segmented_reductions_match_scatter(rng, dtype, op):
    """segmented_reduce_sorted on rows that are not four bytes wide — the
    ``lax.associative_scan`` branch, which 1- and 2-byte min / max reach on
    a TPU — must agree with the scatter it stands in for."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu.ops import segments

    n = 500
    x = (rng.random(n) * 100 - 50).astype(dtype)
    starts = rng.random(n) < 0.05
    starts[0] = True
    seg = np.cumsum(starts) - 1
    groups = int(seg[-1]) + 1
    end = np.full(n, 1, np.int32)
    end[:groups] = np.searchsorted(seg, np.arange(groups), side="right")
    got = np.asarray(jax.jit(segments.segmented_reduce_sorted,
                             static_argnames="op")(
        jnp.asarray(x), jnp.asarray(starts), jnp.asarray(end), op=op))
    scatter = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}[op]
    exp = np.asarray(scatter(jnp.asarray(x), jnp.asarray(seg), groups))
    assert got.dtype == exp.dtype == np.dtype(dtype)
    if dtype is np.float64:
        np.testing.assert_allclose(got[:groups], exp, rtol=1e-12)
    else:
        np.testing.assert_array_equal(got[:groups], exp)
