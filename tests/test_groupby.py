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


# -- the key columns ride the sort and the compaction of the group starts ----

def _parent_keys(cols, count, key_idx, lexsort):
    """PR 31's key columns, the plain reference: the group leaders'
    positions out of the compaction's index, through the sort's
    permutation, then ``Column.take`` a key column."""
    import jax.numpy as jnp
    from cylon_tpu.ops import keys, segments

    cap = cols[0].data.shape[0]
    key_cols = [cols[i] for i in key_idx]
    if lexsort:
        perm, sorted_ops, _ = keys.lexsort_indices(
            keys.build_operands(key_cols, count, cap), cap)
    else:
        operands = [keys.padding_operand(cap, count)]
        for kc in key_cols:
            operands.extend(keys.column_operands(kc))
        perm = jnp.arange(cap, dtype=jnp.int32)
        sorted_ops = keys.pack_operands(operands)
    new_group = ~keys.rows_equal_adjacent(sorted_ops)
    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    start, _ = segments.segment_spans(new_group)
    num_groups = jnp.where(
        count > 0, jnp.take(gid, jnp.clip(count - 1, 0, cap - 1)) + 1, 0)
    leader_src = jnp.take(perm, jnp.clip(start, 0, cap - 1))
    group_live = jnp.arange(cap, dtype=jnp.int32) < num_groups
    return [kc.take(leader_src, valid_mask=group_live)
            for kc in key_cols], num_groups


def _key_case(case, rng):
    """(columns, count, key_idx, value_idx) of one case."""
    from cylon_tpu import column as colmod

    def ints(n, cap, hi, dtype=np.int64, nulls=0.0):
        validity = rng.random(n) > nulls if nulls else None
        return colmod.from_numpy(rng.integers(0, hi, n).astype(dtype),
                                 validity=validity, capacity=cap)

    def floats(n, cap, nulls=0.2):
        return colmod.from_numpy(rng.random(n), validity=rng.random(n) > nulls,
                                 capacity=cap)

    if case == "nulls_in_keys":
        n, cap = 230, 256
        return (ints(n, cap, 40, nulls=0.15), floats(n, cap)), n, (0,), (1,)
    if case == "string_beside_int":
        n, cap = 200, 256
        words = np.array([f"w{x}" * (1 + x % 3)
                          for x in rng.integers(0, 6, n)], dtype=object)
        words[rng.random(n) < 0.1] = None
        return (ints(n, cap, 5, np.int32, nulls=0.1),
                colmod.from_numpy(words, capacity=cap),
                floats(n, cap)), n, (0, 1), (2,)
    if case == "float_keys":    # -0.0 groups with 0.0: the leader's bits stay
        n, cap = 100, 128
        k = rng.choice(np.array([0.0, -0.0, 1.5, np.nan, -np.inf]), n)
        return (colmod.from_numpy(k, validity=np.ones(n, bool), capacity=cap),
                floats(n, cap)), n, (0,), (1,)
    if case == "33_columns":    # two validity words, lanes past the budget
        n, cap = 120, 128
        cols = tuple(ints(n, cap, 2, np.int32, nulls=0.1 if i % 3 else 0.0)
                     for i in range(16)) + (ints(n, cap, 3),) + tuple(
                         ints(n, cap, 9, np.int32, nulls=0.2)
                         for _ in range(16))
        return cols, n, tuple(range(17)), tuple(range(17, 33))
    n, cap = {"count_0": (0, 64), "count_is_capacity": (128, 128)}[case]
    return (ints(n, cap, 30), floats(n, cap, 0.0)), n, (0,), (1,)


KEY_CASES = ["nulls_in_keys", "string_beside_int", "float_keys", "33_columns",
             "count_0", "count_is_capacity"]


@pytest.mark.parametrize("mode", ["scatter", "sort"])
@pytest.mark.parametrize("kernel", ["hash_groupby", "pipeline_groupby"])
@pytest.mark.parametrize("case", KEY_CASES)
def test_key_columns_equal_the_parents(realize, case, kernel, mode):
    """Every buffer of the key columns, bit for bit, in both rows of
    ``ops/realization.py``; the aggregates against the same kernel over
    PR 31's compactions.  ``pipeline_groupby`` takes the rows as they lie:
    a run of equal keys is a group, sorted input or not."""
    import jax.numpy as jnp
    from cylon_tpu.ops import groupby as gmod, realization
    from tests.test_join_lanes import _assert_same_buffers
    from tests.test_permute_modes import index_then_take

    cols, n, key_idx, value_idx = _key_case(case, np.random.default_rng(31))
    count = jnp.asarray(n, jnp.int32)
    aggs = tuple((i, gmod.AggOp.SUM) for i in value_idx)
    with realize(realization.current()._replace(permute=mode)):
        got, groups = getattr(gmod, kernel)(cols, count, key_idx, aggs)
        want, want_groups = _parent_keys(cols, count, key_idx,
                                         kernel == "hash_groupby")
        _assert_same_buffers(got[:len(key_idx)], groups, want, want_groups)
        with index_then_take():
            plain, plain_groups = getattr(gmod, kernel)(cols, count, key_idx,
                                                        aggs)
        _assert_same_buffers(got, groups, plain, plain_groups)
    if case == "count_0":
        assert int(groups) == 0
    if case == "count_is_capacity" and kernel == "hash_groupby":
        assert int(groups) == len(np.unique(np.asarray(cols[0].data)))


@pytest.mark.parametrize("kernel", ["hash_groupby", "pipeline_groupby"])
@pytest.mark.parametrize("case,gathers", [
    ("nulls_in_keys", 0), ("float_keys", 0), ("string_beside_int", 1)])
def test_a_key_that_can_ride_goes_through_no_index(realize, case, gathers,
                                                   kernel):
    """The TPU's row: no gather under ``groupby.keys`` but a string key's
    byte matrix, and no ``pred`` vector through any index."""
    import jax
    import jax.numpy as jnp
    from cylon_tpu.obs import metrics
    from cylon_tpu.ops import groupby as gmod, realization
    from tests.test_join_lanes import _gathers

    cols, n, key_idx, value_idx = _key_case(case, np.random.default_rng(31))
    aggs = tuple((i, gmod.AggOp.SUM) for i in value_idx)
    with realize(realization.current()._replace(permute="sort")):
        before = metrics.counter_value("compact.payload_lanes")
        jaxpr = jax.make_jaxpr(lambda c: getattr(gmod, kernel)(
            c, jnp.asarray(n, jnp.int32), key_idx, aggs))(cols)
        rode = metrics.counter_value("compact.payload_lanes") - before
    found = list(_gathers(jaxpr.jaxpr))
    assert [stage for stage, _ in found].count("groupby.keys") == gathers
    assert not any(dtype == jnp.bool_ for _, dtype in found)
    # a validity word, the key's data; the string's lengths and the order
    # its byte matrix is taken through (the group starts where the rows
    # lie in group order already)
    assert rode == {"nulls_in_keys": 3, "float_keys": 3,
                    "string_beside_int": 3 + (kernel == "hash_groupby")}[case]
