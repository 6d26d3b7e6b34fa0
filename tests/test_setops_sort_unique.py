"""Set ops, sort, unique, shuffle, scalar aggregates — local + distributed.

Mirrors cpp/test/set_op_test.cpp, table_op_test.cpp, partition_test.cpp.
"""
import numpy as np
import pandas as pd
import pytest

from cylon_tpu import SortOptions, Table


# ---------------------------------------------------------------- set ops
def _set_frames(rng):
    a = pd.DataFrame({"k": rng.integers(0, 30, 80), "v": rng.integers(0, 3, 80)})
    b = pd.DataFrame({"k": rng.integers(15, 45, 60), "v": rng.integers(0, 3, 60)})
    return a, b


def _rowset(df):
    return set(map(tuple, df.to_numpy().tolist()))


@pytest.mark.parametrize("world", [1, 2, pytest.param(4, marks=pytest.mark.slow)])
def test_set_ops(request, rng, world):
    ctx = request.getfixturevalue("local_ctx" if world == 1 else f"ctx{world}")
    pa_, pb_ = _set_frames(rng)
    a = Table.from_pandas(pa_, ctx=ctx)
    b = Table.from_pandas(pb_, ctx=ctx)
    sa, sb = _rowset(pa_), _rowset(pb_)
    if world == 1:
        union, inter, sub = a.union(b), a.intersect(b), a.subtract(b)
    else:
        union = a.distributed_union(b)
        inter = a.distributed_intersect(b)
        sub = a.distributed_subtract(b)
    assert _rowset(union.to_pandas()) == sa | sb
    assert union.row_count == len(sa | sb)
    assert _rowset(inter.to_pandas()) == sa & sb
    assert _rowset(sub.to_pandas()) == sa - sb


# ---------------------------------------------------------------- sort
def test_local_sort_multi_col(local_ctx, rng):
    df = pd.DataFrame({"a": rng.integers(0, 5, 50), "b": rng.random(50)})
    t = Table.from_pandas(df, ctx=local_ctx).sort(["a", "b"])
    exp = df.sort_values(["a", "b"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(t.to_pandas(), exp)


def test_local_sort_descending(local_ctx, rng):
    df = pd.DataFrame({"a": rng.integers(0, 100, 40)})
    t = Table.from_pandas(df, ctx=local_ctx).sort("a", ascending=False)
    assert (np.diff(t.to_pandas()["a"].to_numpy()) <= 0).all()


def test_local_sort_strings(local_ctx):
    vals = ["pear", "apple", "fig", "apple", "banana"]
    t = Table.from_pydict({"s": vals}).sort("s")
    assert t.to_pydict()["s"] == sorted(vals)


def _nullable(rng, values, share=0.25):
    """``values`` as float64 / object with a share of them missing."""
    missing = rng.random(len(values)) < share
    if values.dtype.kind in "OU":
        return np.where(missing, None, values.astype(object))
    return np.where(missing, np.nan, values).astype(
        values.dtype if values.dtype.kind == "f" else np.float64)


def _payload_frame(case, rng):
    """(frame, capacity) for one case of what rides the sort and what is
    left to the take; ``k`` and ``i`` are the keys, with nulls in ``k``."""
    n = {"count_0": 0, "count_capacity": 64}.get(case, 50)
    cols = {"k": _nullable(rng, rng.integers(0, 5, n).astype(np.float64)),
            "i": rng.integers(-3, 3, n),
            "v": _nullable(rng, rng.random(n).astype(np.float32)),
            "b": rng.random(n) > 0.5}
    if case == "string_beside":       # byte matrix takes, the rest rides
        cols["s"] = _nullable(rng, np.array([f"w{j % 7}" for j in range(n)]))
    elif case == "33_columns":        # two words of validity bits
        for j in range(31):
            cols[f"c{j}"] = _nullable(rng, rng.random(n).astype(np.float32))
    elif case == "wider_than_lanes":  # 64-bit lanes past the sort's budget
        for j in range(8):
            cols[f"w{j}"] = _nullable(rng, rng.random(n))
            cols[f"x{j}"] = rng.integers(-2 ** 40, 2 ** 40, n)
    return pd.DataFrame(cols), 64


PAYLOAD_CASES = ["nulls", "string_beside", "33_columns", "wider_than_lanes",
                 "count_0", "count_capacity"]


@pytest.mark.parametrize("case", PAYLOAD_CASES)
def test_sort_rows_matches_pandas(local_ctx, rng, case):
    """Every buffer comes back in the key order, nulls included, whether it
    rode the sort or went through the permutation."""
    from cylon_tpu.ops import keys

    df, cap = _payload_frame(case, rng)
    t = Table.from_pandas(df, ctx=local_ctx, capacity=cap)
    lanes, layout = keys.pack_payload(
        [b for c in t.columns for b in (c.data, c.validity, c.lengths)
         if b is not None])
    assert (None in layout) == (case in (
        "string_beside", "33_columns", "wider_than_lanes"))
    assert len(lanes) > len(df.columns) / 32
    got = t.sort(["k", "i"], ascending=[False, True]).to_pandas()
    exp = df.sort_values(["k", "i"], ascending=[False, True],
                         na_position="first", kind="stable")
    pd.testing.assert_frame_equal(got, exp.reset_index(drop=True),
                                  check_dtype=False)


@pytest.mark.parametrize("world", [2, pytest.param(4, marks=pytest.mark.slow), pytest.param(8, marks=pytest.mark.slow)])
def test_distributed_sort(request, rng, world):
    ctx = request.getfixturevalue(f"ctx{world}")
    df = pd.DataFrame({"a": rng.integers(0, 1000, 500), "b": rng.random(500)})
    t = Table.from_pandas(df, ctx=ctx).distributed_sort("a")
    got = t.to_pandas()  # gather concatenates shards in mesh order
    assert len(got) == len(df)
    assert (np.diff(got["a"].to_numpy()) >= 0).all()
    assert sorted(got["a"]) == sorted(df["a"])


@pytest.mark.parametrize("world", [2, pytest.param(4, marks=pytest.mark.slow), pytest.param(8, marks=pytest.mark.slow)])
def test_distributed_sort_string_lead(request, rng, world):
    """Global sort on a STRING lead column — beyond the reference (its
    RangePartitionKernel is numeric only): the range partitioner bins on
    the 4-byte prefix; adversarial shared prefixes only hurt balance."""
    ctx = request.getfixturevalue(f"ctx{world}")
    n = 2000
    words = np.array([f"w{rng.integers(0, 500):04d}" for _ in range(n)],
                     object)
    # shared-prefix block stressing bin merging
    words[: n // 4] = np.array(
        [f"aaaa{rng.integers(0, 99):02d}" for _ in range(n // 4)], object)
    df = pd.DataFrame({"s": words, "v": rng.random(n)})
    t = Table.from_pandas(df, ctx=ctx).distributed_sort("s")
    got = t.to_pandas()["s"].tolist()
    assert got == sorted(words)


def test_distributed_sort_descending(request, rng, ctx4):
    df = pd.DataFrame({"a": rng.random(300)})
    t = Table.from_pandas(df, ctx=ctx4).distributed_sort(
        "a", options=SortOptions(ascending=False))
    got = t.to_pandas()["a"].to_numpy()
    assert (np.diff(got) <= 0).all()


# ---------------------------------------------------------------- unique
@pytest.mark.parametrize("world", [1, 2, 4])
def test_unique(request, rng, world):
    ctx = request.getfixturevalue("local_ctx" if world == 1 else f"ctx{world}")
    df = pd.DataFrame({"a": rng.integers(0, 20, 100)})
    t = Table.from_pandas(df, ctx=ctx)
    u = t.unique() if world == 1 else t.distributed_unique()
    assert sorted(u.to_pandas()["a"]) == sorted(df["a"].unique())


def test_unique_keep_first_order(local_ctx):
    t = Table.from_pydict({"a": [3, 1, 3, 2, 1]}, ctx=local_ctx)
    assert t.unique().to_pydict()["a"] == [3, 1, 2]
    assert t.unique(keep="last").to_pydict()["a"] == [3, 2, 1]


def test_unique_subset_columns(local_ctx):
    t = Table.from_pydict({"a": [1, 1, 2], "b": [9, 8, 7]}, ctx=local_ctx)
    u = t.unique(columns=["a"])
    assert u.to_pydict() == {"a": [1, 2], "b": [9, 7]}


# ---------------------------------------------------------------- shuffle
@pytest.mark.parametrize("world", [2, 4, 8])
def test_shuffle_preserves_rows_and_colocates(request, rng, world):
    ctx = request.getfixturevalue(f"ctx{world}")
    df = pd.DataFrame({"k": rng.integers(0, 37, 300), "v": rng.random(300)})
    t = Table.from_pandas(df, ctx=ctx)
    sh = t.shuffle("k")
    assert sh.row_count == len(df)
    got = sh.to_pandas()
    assert _rowset(got.round(9)) == _rowset(df.round(9))
    # keys must be colocated: each key appears in exactly one shard
    import jax

    counts = np.asarray(jax.device_get(sh.row_counts))
    cap = sh.shard_capacity
    kdata = np.asarray(jax.device_get(sh.columns[0].data))
    shard_of_key = {}
    for s in range(world):
        for val in kdata[s * cap: s * cap + counts[s]]:
            assert shard_of_key.setdefault(int(val), s) == s


# ------------------------------------------------------- scalar aggregates
@pytest.mark.parametrize("world", [1, 4])
def test_scalar_aggregates(request, rng, world):
    ctx = request.getfixturevalue("local_ctx" if world == 1 else f"ctx{world}")
    df = pd.DataFrame({"v": rng.random(200) * 100 - 50})
    t = Table.from_pandas(df, ctx=ctx)
    assert np.isclose(float(t.sum("v")), df["v"].sum())
    assert np.isclose(float(t.min("v")), df["v"].min())
    assert np.isclose(float(t.max("v")), df["v"].max())
    assert int(t.count("v")) == len(df)
