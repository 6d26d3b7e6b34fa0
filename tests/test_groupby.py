"""Group-by tests: local + distributed two-phase, all aggregation ops.

Mirrors the reference groupby suites (cpp/test/groupby_test.cpp,
python/test/test_aggregate.py) with pandas as the golden engine.
"""
import numpy as np
import pandas as pd
import pytest

from cylon_tpu import Table
from tests.test_setops_sort_unique import PAYLOAD_CASES, _payload_frame


def _check(t, df, ops, ddof=0):
    g = t.groupby("g", {"v": ops}, ddof=ddof).to_pandas().sort_values("g").reset_index(drop=True)
    grp = df.groupby("g")["v"]
    exp = {"sum": grp.sum(), "mean": grp.mean(), "count": grp.count(),
           "min": grp.min(), "max": grp.max(), "var": grp.var(ddof=ddof),
           "std": grp.std(ddof=ddof)}
    for op in ops:
        col = f"{'stddev' if op == 'std' else op}_v"
        want = exp[op].sort_index().to_numpy(dtype=float)
        got = g[col].to_numpy(dtype=float)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12,
                                   err_msg=f"op={op}")


def test_local_groupby_all_ops(local_ctx, rng):
    df = pd.DataFrame({"g": rng.integers(0, 7, 100), "v": rng.random(100)})
    t = Table.from_pandas(df, ctx=local_ctx)
    _check(t, df, ["sum", "mean", "count", "min", "max", "var", "std"])


@pytest.mark.parametrize("world", [2, 4, 8])
def test_distributed_groupby(request, rng, world):
    ctx = request.getfixturevalue(f"ctx{world}")
    df = pd.DataFrame({"g": rng.integers(0, 13, 400), "v": rng.random(400)})
    t = Table.from_pandas(df, ctx=ctx)
    _check(t, df, ["sum", "mean", "count", "min", "max", "var", "std"])


def test_groupby_multi_key(local_ctx, rng):
    df = pd.DataFrame({"g1": rng.integers(0, 4, 80), "g2": rng.integers(0, 4, 80),
                       "v": rng.random(80)})
    t = Table.from_pandas(df, ctx=local_ctx)
    g = t.groupby(["g1", "g2"], {"v": "sum"}).to_pandas() \
         .sort_values(["g1", "g2"]).reset_index(drop=True)
    exp = df.groupby(["g1", "g2"])["v"].sum().reset_index()
    np.testing.assert_allclose(g["sum_v"], exp["v"], rtol=1e-9)


def test_groupby_int_values(local_ctx, rng):
    df = pd.DataFrame({"g": rng.integers(0, 5, 60),
                       "v": rng.integers(-100, 100, 60)})
    t = Table.from_pandas(df, ctx=local_ctx)
    g = t.groupby("g", {"v": ["sum", "min", "max"]}).to_pandas() \
         .sort_values("g").reset_index(drop=True)
    grp = df.groupby("g")["v"]
    assert (g["sum_v"].to_numpy() == grp.sum().sort_index().to_numpy()).all()
    assert (g["min_v"].to_numpy() == grp.min().sort_index().to_numpy()).all()
    assert (g["max_v"].to_numpy() == grp.max().sort_index().to_numpy()).all()


@pytest.mark.parametrize("case", PAYLOAD_CASES)
def test_hash_groupby_matches_pandas(local_ctx, rng, case):
    """The value columns arrive in group order with their nulls, whether
    they rode the group-by's sort or went through its permutation."""
    df, cap = _payload_frame(case, rng)
    t = Table.from_pandas(df, ctx=local_ctx, capacity=cap)
    numeric = [c for c in df.columns if c not in ("k", "i", "b", "s")]
    aggs = {c: ["sum", "count", "max"] for c in numeric}
    named = {f"{op}_{c}": (c, op) for c in numeric for op in aggs[c]}
    if "s" in df:
        aggs["s"] = ["nunique"]
        named["nunique_s"] = ("s", "nunique")
    got = t.groupby(["k", "i"], aggs).to_pandas()
    exp = df.groupby(["k", "i"], dropna=False).agg(**named).reset_index()
    exp = exp.sort_values(["k", "i"], na_position="first")
    assert list(got.columns) == ["k", "i"] + list(named)
    assert len(got) == len(exp)
    for name, (c, op) in named.items():
        want = exp[name].to_numpy(dtype=float)
        if op == "sum":     # a sum of no values is null here, 0 in pandas
            want = np.where(exp[f"count_{c}"] == 0, np.nan, want)
        np.testing.assert_allclose(got[name].to_numpy(dtype=float), want,
                                   rtol=1e-6, err_msg=name)
    for key in ("k", "i"):
        np.testing.assert_array_equal(got[key].to_numpy(dtype=float),
                                      exp[key].to_numpy(dtype=float))


def test_groupby_nunique_local(local_ctx):
    df = pd.DataFrame({"g": [1, 1, 1, 2, 2], "v": [5, 5, 6, 7, 7]})
    t = Table.from_pandas(df, ctx=local_ctx)
    g = t.groupby("g", {"v": "nunique"}).to_pandas().sort_values("g")
    assert g["nunique_v"].tolist() == [2, 1]


def test_groupby_nulls_excluded(local_ctx):
    pa = pytest.importorskip("pyarrow")
    at = pa.table({"g": pa.array([1, 1, 2, 2]),
                   "v": pa.array([1.0, None, 3.0, None])})
    t = Table.from_arrow(at, ctx=local_ctx)
    g = t.groupby("g", {"v": ["sum", "count", "mean"]}).to_pandas().sort_values("g")
    assert g["count_v"].tolist() == [1, 1]
    assert g["sum_v"].tolist() == [1.0, 3.0]


def test_pipeline_groupby_on_sorted(local_ctx):
    """reference: DistributedPipelineGroupBy assumes key-sorted input."""
    from cylon_tpu.ops import groupby as gmod
    import jax.numpy as jnp

    df = pd.DataFrame({"g": [1, 1, 2, 3, 3, 3], "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]})
    t = Table.from_pandas(df, ctx=local_ctx)
    cols, m = gmod.pipeline_groupby(t.columns, t.row_counts[0], (0,),
                                    ((1, gmod.AggOp.SUM),))
    assert int(m) == 3
    np.testing.assert_allclose(np.asarray(cols[1].data[:3]), [3.0, 3.0, 15.0])


def test_groupby_single_group(local_ctx):
    t = Table.from_pydict({"g": [7, 7, 7], "v": [1.0, 2.0, 3.0]}, ctx=local_ctx)
    g = t.groupby("g", {"v": "mean"})
    assert g.row_count == 1
    assert g.to_pydict()["mean_v"] == [2.0]


def test_distributed_pipeline_groupby(ctx4, rng):
    """DistributedPipelineGroupBy (reference: groupby/groupby.cpp:75-114):
    per-shard key-sorted input -> pipeline partial -> shuffle -> sort ->
    pipeline final; must agree with the hash path and pandas."""
    import pandas as pd
    from cylon_tpu import Table
    from tests.utils import assert_rows_equal

    n = 400
    k = np.sort(rng.integers(0, 40, n)).astype(np.int64)  # pre-sorted keys
    v = rng.random(n)
    df = pd.DataFrame({"k": k, "v": v})
    # each shard must individually be key-sorted: distribute contiguous runs
    t = Table.from_pydict({"k": k, "v": v}, ctx=ctx4)

    out = t.groupby("k", {"v": ["sum", "mean", "count"]},
                    groupby_type="pipeline")
    ref = (df.groupby("k").agg(sum_v=("v", "sum"), mean_v=("v", "mean"),
                               count_v=("v", "count")).reset_index())
    assert_rows_equal(out, ref, ndigits=6)

    hash_out = t.groupby("k", {"v": ["sum", "mean", "count"]})
    assert hash_out.row_count == out.row_count


def test_local_pipeline_groupby_table(local_ctx, rng):
    import pandas as pd
    from cylon_tpu import Table
    from tests.utils import assert_rows_equal

    k = np.sort(rng.integers(0, 11, 100)).astype(np.int64)
    v = rng.random(100)
    t = Table.from_pydict({"k": k, "v": v}, ctx=local_ctx)
    out = t.groupby("k", {"v": ["min", "max"]}, groupby_type="pipeline")
    ref = (pd.DataFrame({"k": k, "v": v}).groupby("k")
           .agg(min_v=("v", "min"), max_v=("v", "max")).reset_index())
    assert_rows_equal(out, ref, ndigits=9)


def test_float_zero_and_nan_key_semantics(local_ctx):
    """-0.0 groups with +0.0 and all NaN payloads form ONE group (pandas
    dropna=False semantics) in every sort-based kernel."""
    import pandas as pd
    from cylon_tpu import Table

    k = np.array([0.0, -0.0, 1.0, np.nan, np.nan, 1.0])
    v = np.arange(6, dtype=np.float64)
    # NaN keys arrive as valid values, not nulls, to exercise raw-NaN keys
    t = Table.from_pydict({"k": k, "v": v}, ctx=local_ctx)
    from cylon_tpu import column as colmod

    kcol = colmod.from_numpy(k, validity=np.ones(6, bool))
    vcol = colmod.from_numpy(v)
    from cylon_tpu.ops import groupby as gmod
    import jax.numpy as jnp

    cols, g = gmod.hash_groupby((kcol, vcol), jnp.asarray(6, jnp.int32),
                                (0,), ((1, gmod.AggOp.COUNT),), 0)
    assert int(g) == 3  # {0.0/-0.0}, {1.0}, {NaN}
    counts = sorted(np.asarray(cols[1].data[:3]).tolist())
    assert counts == [2, 2, 2]

    from cylon_tpu.ops import unique as umod

    ucols, m = umod.unique((kcol,), jnp.asarray(6, jnp.int32), (0,), "first")
    assert int(m) == 3
