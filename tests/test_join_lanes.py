"""The join's takes: thirteen lanes through its indices, not seventeen.

``join_gather`` sends a side's validity vectors through the index as bits
of a word and one lane a left row through the expansion.  The plain form
stays in the repo as ``Column.take(idx, valid_mask=...)``, a ``pred``
vector a column: ``join_indices`` and a plain take a column are the
reference here, and every output buffer has to agree bit for bit — data,
validity, lengths and the row count — for every join type and algorithm,
in both rows of ``ops/realization.py``.  The lane counts are read from
jaxprs: no chip is needed to see that a gather went.
"""
import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from cylon_tpu import Table
from cylon_tpu import column as colmod
from cylon_tpu.config import JoinType
from cylon_tpu.obs import STAGES, metrics
from cylon_tpu.ops import common, compact
from cylon_tpu.ops import join as jmod
from cylon_tpu.ops import realization
from tests.test_permute_modes import index_then_take

MODES = ("scatter", "sort")
HOW = {JoinType.INNER: "inner", JoinType.LEFT: "left",
       JoinType.RIGHT: "right", JoinType.FULL_OUTER: "outer"}


def _side(rng, n, cap, keys, value_name, nulls=True, strings=True, wide=0):
    """(names, columns) of one side: an int64 key and a float64 value with
    nulls in both, a string column beside, ``wide`` more value columns."""
    def validity(p):
        return rng.random(n) > p if nulls else None

    names = ["k", value_name]
    cols = [colmod.from_numpy(rng.integers(0, keys, n).astype(np.int64),
                              validity=validity(0.1), capacity=cap),
            colmod.from_numpy(rng.random(n), validity=validity(0.2),
                              capacity=cap)]
    if strings:
        words = np.array([f"w{x}" * (1 + x % 3)
                          for x in rng.integers(0, 40, n)], dtype=object)
        names.append(value_name + "_s")
        cols.append(colmod.from_numpy(words, capacity=cap))
    for i in range(wide):
        names.append(f"{value_name}{i}")
        cols.append(colmod.from_numpy(
            rng.integers(0, 9, n).astype(np.int32),
            validity=validity(0.3) if i % 2 else None, capacity=cap))
    return tuple(names), tuple(cols)


def _tables(ctx, seed, nl, nr, cap, keys, **kw):
    rng = np.random.default_rng(seed)
    tables = []
    for n, value_name in ((nl, "a"), (nr, "b")):
        names, cols = _side(rng, n, cap, keys, value_name, **kw)
        tables.append(Table(cols, jnp.asarray([n], jnp.int32), names, ctx))
    return tables


def _plain(left, right, jt, out_cap, algo, key_grouped=False):
    """The join as PR 29 took its rows: the slot -> row indices, then
    ``Column.take`` a column, validity vector and all."""
    lidx, ridx, lvalid, rvalid, m = jmod.join_indices(
        left.columns, left.row_counts[0], right.columns, right.row_counts[0],
        (0,), (0,), jt, out_cap, algo, key_grouped)
    return (tuple(c.take(lidx, valid_mask=lvalid) for c in left.columns)
            + tuple(c.take(ridx, valid_mask=rvalid)
                    for c in right.columns)), m


def _fused(left, right, jt, out_cap, algo, key_grouped=False):
    return jmod.join_gather(
        left.columns, left.row_counts[0], right.columns, right.row_counts[0],
        (0,), (0,), jt, out_cap, algo, key_grouped)


def _assert_same_buffers(got, got_count, want, want_count):
    assert int(got_count) == int(want_count)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        for name in ("data", "validity", "lengths"):
            a, b = getattr(g, name), getattr(w, name)
            assert (a is None) == (b is None), name
            if a is not None:
                a, b = np.asarray(a), np.asarray(b)
                assert a.dtype == b.dtype and a.shape == b.shape, name
                assert a.tobytes() == b.tobytes(), name


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("algo", ["sort", "hash"])
@pytest.mark.parametrize("jt", list(HOW), ids=lambda jt: jt.name)
def test_table_join_equals_plain_takes(realize, local_ctx, jt, algo, mode):
    """``Table.join`` against the plain takes at the capacity the table
    chose: nulls in keys and values, a string column beside."""
    with realize(realization.current()._replace(permute=mode)):
        left, right = _tables(local_ctx, 11, 230, 190, 256, 60)
        out = left.join(right, on="k", how=HOW[jt], algorithm=algo)
        want, want_count = _plain(left, right, jt, out.capacity, algo)
        _assert_same_buffers(out.columns, out.row_counts[0], want, want_count)
        assert int(want_count) == len(_pandas(left).merge(
            _pandas(right), on="k", how=HOW[jt]))


def _pandas(table):
    """The side's keys as pandas can merge them: a null key joins a null
    key (``ops/keys.py``, the reference's semantics), so it reads -1."""
    n = int(table.row_counts[0])
    key = table.columns[0]
    return pd.DataFrame({"k": np.where(np.asarray(key.validity)[:n],
                                       np.asarray(key.data)[:n], -1)})


CASES = {
    # nl, nr, cap, keys, out_cap, keyword arguments of _side
    "count_0": (0, 0, 64, 5, 128, {}),
    "count_is_capacity": (128, 128, 128, 40, 1024, {}),
    "many_to_many": (90, 80, 128, 2, 1 << 13, {}),
    "two_validity_words": (100, 90, 128, 30, 1024, {"wide": 33}),
    "out_capacity_too_small": (120, 110, 128, 10, 256, {}),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.FULL_OUTER],
                         ids=lambda jt: jt.name)
@pytest.mark.parametrize("case", list(CASES))
def test_join_gather_equals_plain_takes(realize, local_ctx, case, jt, mode):
    nl, nr, cap, keys, out_cap, kw = CASES[case]
    with realize(realization.current()._replace(permute=mode)):
        left, right = _tables(local_ctx, 5, nl, nr, cap, keys, **kw)
        got, got_count = _fused(left, right, jt, out_cap, "sort")
        want, want_count = _plain(left, right, jt, out_cap, "sort")
        _assert_same_buffers(got, got_count, want, want_count)
        exact = int(jmod.join_row_count(
            left.columns, left.row_counts[0], right.columns,
            right.row_counts[0], (0,), (0,), jt, "sort"))
        # a capacity too small truncates the rows, never the count
        assert int(got_count) == exact
        assert (exact > out_cap) == (case == "out_capacity_too_small")
        if case == "count_0":
            assert exact == 0 and not any(
                np.asarray(c.validity).any() for c in got)
        if case == "two_validity_words":
            assert len(left.columns) > 32
            assert jmod.take_lanes(left.columns) == 2 + 2 + 2 + 8 + 1 + 33


@pytest.mark.parametrize("algo", ["sort", "hash"])
def test_key_grouped_equals_plain_takes(local_ctx, algo):
    left, right = _tables(local_ctx, 3, 200, 180, 256, 50, nulls=False)
    got, got_count = _fused(left, right, JoinType.INNER, 2048, algo, True)
    want, want_count = _plain(left, right, JoinType.INNER, 2048, algo, True)
    _assert_same_buffers(got, got_count, want, want_count)
    keys = np.asarray(got[0].data)[:int(got_count)]
    assert len(np.flatnonzero(np.diff(keys))) + 1 == len(np.unique(keys))


def test_table_join_survives_a_cached_capacity_too_small(local_ctx):
    """The second join at a site meets the first's capacity: the takes run
    truncated, the count says so, and the join runs again at the size."""
    few, many = [_tables(local_ctx, 7, 100, 100, 128, keys, nulls=False,
                         strings=False) for keys in (100, 2)]
    assert few[0].join(few[1], on="k").capacity < 1000
    out = many[0].join(many[1], on="k")
    want, want_count = _plain(many[0], many[1], JoinType.INNER, out.capacity,
                              "sort")
    assert int(want_count) > 2000
    _assert_same_buffers(out.columns, out.row_counts[0], want, want_count)


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_join_on_the_mesh_equals_pandas(ctx4, how):
    """Four shards: the same join under ``shard_map``."""
    rng = np.random.default_rng(17)
    n = 900
    ldf = pd.DataFrame({"k": rng.integers(0, 250, n), "a": rng.random(n),
                        "s": [f"w{x}" for x in rng.integers(0, 30, n)]})
    rdf = pd.DataFrame({"k": rng.integers(0, 250, n), "b": rng.random(n)})
    got = Table.from_pandas(ldf, ctx=ctx4).distributed_join(
        Table.from_pandas(rdf, ctx=ctx4), on="k", how=how).to_pandas()
    want = ldf.merge(rdf, on="k", how=how)
    assert len(got) == len(want)
    key = np.where(got["l_k"].isna(), got["r_k"], got["l_k"])
    got = got.assign(k=key)[["k", "a", "s", "b"]]
    order = ["k", "a", "b"]
    got = got.sort_values(order).reset_index(drop=True)
    want = want[["k", "a", "s", "b"]].sort_values(order).reset_index(
        drop=True)
    np.testing.assert_array_equal(got["k"].to_numpy(np.int64),
                                  want["k"].to_numpy(np.int64))
    for name in ("a", "b"):
        np.testing.assert_array_equal(got[name].to_numpy(float),
                                      want[name].to_numpy(float))
    assert (got["s"].fillna("") == want["s"].fillna("")).all()


RANGES_CASES = {
    # nl, nr, cap, keys
    "nulls_in_keys": (230, 190, 256, 60),
    "count_0": (0, 0, 64, 5),
    "count_is_capacity": (128, 128, 128, 40),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("jt", list(HOW), ids=lambda jt: jt.name)
@pytest.mark.parametrize("case", list(RANGES_CASES))
def test_right_order_and_left_key_order_equal_the_parents(
        realize, local_ctx, case, jt, mode):
    """``perm_r`` and ``left_key_order`` ride the partition of the combined
    sort's entries; PR 31 took them out of the sort's permutation through
    the partition's index, and that stays here as the plain reference."""
    nl, nr, cap, keys = RANGES_CASES[case]
    with realize(realization.current()._replace(permute=mode)):
        left, right = _tables(local_ctx, 13, nl, nr, cap, keys)
        sides = (left.columns, left.row_counts[0], right.columns,
                 right.row_counts[0], (0,), (0,))
        _, _, perm_r, _, _, left_key_order = jmod._match_ranges(*sides, jt)
        perm = common.combined_sorted_runs(*sides)[0]
        part, _ = compact.partition_indices(perm >= cap)
        want_r = jnp.take(perm, part[:cap]) - cap
        want_l = jnp.take(perm, part[cap:])
        for got, want in ((perm_r, want_r), (left_key_order, want_l)):
            assert got.dtype == want.dtype == jnp.int32
            np.testing.assert_array_equal(got, want)
        assert sorted(np.asarray(perm_r)) == list(range(cap))
        assert sorted(np.asarray(left_key_order)) == list(range(cap))


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("jt,key_grouped", [
    (jt, False) for jt in HOW] + [(JoinType.INNER, True)],
    ids=lambda v: getattr(v, "name", f"key_grouped_{v}"))
def test_join_equals_the_join_with_index_then_take(realize, local_ctx, jt,
                                                   key_grouped, mode):
    """Every output buffer of ``join_gather`` and ``join_indices`` against
    the same kernels over PR 31's compactions."""
    with realize(realization.current()._replace(permute=mode)):
        left, right = _tables(local_ctx, 29, 210, 240, 256, 50)
        args = (left.columns, left.row_counts[0], right.columns,
                right.row_counts[0], (0,), (0,), jt, 2048, "sort",
                key_grouped)
        got, got_count = jmod.join_gather(*args)
        got_indices = jmod.join_indices(*args)
        with index_then_take():
            want, want_count = jmod.join_gather(*args)
            want_indices = jmod.join_indices(*args)
        _assert_same_buffers(got, got_count, want, want_count)
        for a, b in zip(got_indices, want_indices):
            assert a.dtype == b.dtype
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _gathers(jaxpr, scope=()):
    """(stage, operand dtype) of every gather in ``jaxpr`` and the jaxprs it
    calls; the stage is the innermost of ``obs.STAGES`` round it."""
    for eqn in jaxpr.eqns:
        names = scope + tuple(str(eqn.source_info.name_stack).split("/"))
        if eqn.primitive.name == "gather":
            named = [part for part in names if part in STAGES]
            yield (named[-1] if named else None), eqn.invars[0].aval.dtype
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _gathers(inner, names)


def _kv(n=64):
    return (colmod.from_numpy(np.arange(n, dtype=np.int64)),
            colmod.from_numpy(np.arange(n, dtype=np.float64)))


@pytest.mark.parametrize("jt", list(HOW), ids=lambda jt: jt.name)
def test_two_lanes_through_the_expansion(realize, jt):
    """The TPU's row: ``join.expand`` sends ``delta`` through ``li`` and the
    right rows through the position, for every join type (the parent sent
    ``base_l``, ``matches``, ``lo`` and ``perm_r``, and an outer join's
    tail beside)."""
    count = jnp.asarray(60, jnp.int32)
    with realize(realization.current()._replace(permute="sort")):
        jaxpr = jax.make_jaxpr(lambda l, r: jmod.join_indices(
            l, count, r, count, (0,), (0,), jt, 256))(_kv(), _kv())
        found = list(_gathers(jaxpr.jaxpr))
    assert [stage for stage, _ in found].count("join.expand") == 2
    assert not any(dtype == jnp.bool_ for _, dtype in found)
    # the key-ordered right rows and the left key order ride the partition
    # (the parent took both out of the permutation: two gathers there)
    assert "join.ranges" not in [stage for stage, _ in found]


@pytest.mark.parametrize("algo", ["sort", "hash"])
@pytest.mark.parametrize("key_grouped", [False, True])
def test_no_validity_vector_goes_through_an_index(key_grouped, algo):
    count = jnp.asarray(60, jnp.int32)
    for jt in [JoinType.INNER] if key_grouped else list(HOW):
        jaxpr = jax.make_jaxpr(lambda l, r: jmod.join_gather(
            l, count, r, count, (0,), (0,), jt, 256, algo, key_grouped))(
                _kv(), _kv())
        found = list(_gathers(jaxpr.jaxpr))
        stages = [stage for stage, _ in found]
        # (int64, float64) a side: two data buffers and one validity word
        assert stages.count("join.gather_left") == 3
        assert stages.count("join.gather_right") == 3
        assert not any(dtype == jnp.bool_ for _, dtype in found)


def test_counter_says_how_many_lanes(local_ctx):
    """(int64, float64) join (int64, float64), the benchmark's query: ten
    lanes through ``lidx`` and ``ridx`` (twelve on PR 29's count)."""
    left, right = _tables(local_ctx, 23, 100, 100, 128, 50, strings=False)
    before = metrics.counter_value("join.take_lanes")
    left.join(right, on="k")   # first call at the site: count, then gather
    assert metrics.counter_value("join.take_lanes") - before == 10
    assert "join.take_lanes" in metrics.snapshot()["counters"]
