"""Scatter-realized vs sort-realized permutation kernels must agree.

compact.permute_mode selects how compactions / partitions / inverse
permutations / the join expansion's slot->row map are materialized:
"scatter" (cumsum destinations + permuting scatter — the XLA:CPU
optimum) or "sort" (packed single-word / key sorts — the TPU optimum;
round-4 hardware profile: a 64M-word ``lax.sort`` runs ~4x faster than a
same-size scatter).  Both must produce identical results on every
consumer (reference behavior being preserved: join.cpp:179-235 output
building, table.cpp:966-1029 unique filter, arrow_kernels.hpp:60-96
splitters).
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from cylon_tpu import column as colmod
from cylon_tpu import config, precision
from cylon_tpu.config import JoinType
from cylon_tpu.ops import compact, join as join_mod, unique as unique_mod
from cylon_tpu.ops import keys, realization, segments


MODES = ("scatter", "sort")


def _per_mode(realize, fn):
    out = []
    for mode in MODES:
        with realize(realization.current()._replace(permute=mode)):
            out.append(fn())
    return out


@pytest.mark.parametrize("cap", [1, 7, 256, 1 << 12])
def test_compact_partition_agree(realize, cap):
    rng = np.random.default_rng(cap)
    mask = jnp.asarray(rng.integers(0, 2, cap).astype(bool))

    def run():
        idx, n = compact.compact_indices(mask)
        perm, nt = compact.partition_indices(mask)
        return (np.asarray(idx), int(n), np.asarray(perm), int(nt))

    a, b = _per_mode(realize, run)
    assert a[1] == b[1] and a[3] == b[3]
    n = a[1]
    # compact contract: first n entries identical; tail is caller-masked
    np.testing.assert_array_equal(a[0][:n], b[0][:n])
    # partition contract: the FULL permutation is pinned (stable partition)
    np.testing.assert_array_equal(a[2], b[2])
    # sort-mode tails must still be in-bounds filler
    assert (b[0] >= 0).all() and (b[0] < cap).all()


def test_inverse_permute_agree(realize):
    rng = np.random.default_rng(42)
    n = 1 << 11
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    f1 = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32))
    f2 = jnp.asarray(rng.integers(0, 5, n).astype(np.int32))

    def run():
        a, b = compact.inverse_permute(perm, f1, f2)
        return np.asarray(a), np.asarray(b)

    (a1, a2), (b1, b2) = _per_mode(realize, run)
    np.testing.assert_array_equal(a1, b1)
    np.testing.assert_array_equal(a2, b2)
    # ground truth
    ref = np.empty(n, np.int32)
    ref[np.asarray(perm)] = np.asarray(f1)
    np.testing.assert_array_equal(a1, ref)


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.LEFT,
                                JoinType.RIGHT, JoinType.FULL_OUTER])
def test_join_gather_agree(realize, jt):
    rng = np.random.default_rng(int(jt.value) + 1)
    cap = 1 << 10
    lk = rng.integers(0, 200, cap).astype(np.int32)
    lv = rng.random(cap).astype(np.float32)
    rk = rng.integers(0, 200, cap).astype(np.int32)
    rv = rng.random(cap).astype(np.float32)
    cols_l = (colmod.from_numpy(lk), colmod.from_numpy(lv))
    cols_r = (colmod.from_numpy(rk), colmod.from_numpy(rv))
    count = jnp.asarray(cap - 13, jnp.int32)

    def run():
        m = int(join_mod.join_row_count(cols_l, count, cols_r, count,
                                        (0,), (0,), jt, "sort"))
        out, n = join_mod.join_gather(cols_l, count, cols_r, count,
                                      (0,), (0,), jt, 1 << 14, "sort")
        n = int(n)
        rows = [tuple(np.asarray(c.data)[:n][i] for c in out)
                for i in range(n)]
        return m, n, sorted(rows)

    a, b = _per_mode(realize, run)
    assert a[0] == b[0] and a[1] == b[1]
    assert a[2] == b[2]


def test_join_key_grouped_agree(realize):
    rng = np.random.default_rng(99)
    cap = 1 << 10
    lk = rng.integers(0, 64, cap).astype(np.int32)
    rk = rng.integers(0, 64, cap).astype(np.int32)
    cols_l = (colmod.from_numpy(lk),)
    cols_r = (colmod.from_numpy(rk),)
    count = jnp.asarray(cap, jnp.int32)

    def run():
        out, n = join_mod.join_gather(cols_l, count, cols_r, count,
                                      (0,), (0,), JoinType.INNER, 1 << 15,
                                      "sort", key_grouped=True)
        n = int(n)
        return n, np.asarray(out[0].data)[:n]

    a, b = _per_mode(realize, run)
    assert a[0] == b[0]
    # key_grouped output order is fully pinned by the combined sort
    np.testing.assert_array_equal(a[1], b[1])


@pytest.mark.parametrize("keep", ["first", "last"])
def test_unique_agree(realize, keep):
    rng = np.random.default_rng(7 if keep == "first" else 8)
    cap = 1 << 11
    vals = rng.integers(0, 100, cap).astype(np.int32)
    cols = (colmod.from_numpy(vals),)
    count = jnp.asarray(cap - 9, jnp.int32)

    def run():
        out, m = unique_mod.unique(cols, count, (0,), keep=keep)
        m = int(m)
        return m, np.asarray(out[0].data)[:m]

    a, b = _per_mode(realize, run)
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])


def test_count_leq_dense_matches_searchsorted():
    rng = np.random.default_rng(3)
    for cap_l, out_cap in ((1, 4), (100, 256), (1000, 2048)):
        emit = rng.integers(0, 4, cap_l).astype(np.int32)
        csum = np.cumsum(emit).astype(np.int32)
        out_cap = max(out_cap, int(csum[-1]))
        got = np.asarray(compact.count_leq_dense(jnp.asarray(csum), out_cap))
        want = np.searchsorted(csum, np.arange(out_cap), side="right")
        np.testing.assert_array_equal(got, want.astype(np.int32))


def test_nunique_agree_across_modes(realize):
    from cylon_tpu.ops import groupby as groupby_mod

    rng = np.random.default_rng(21)
    cap = 1 << 11
    n = cap - 30
    keys_np = rng.integers(0, 40, cap).astype(np.int32)
    vals_np = rng.integers(0, 15, cap).astype(np.int32)
    kcol = colmod.from_numpy(keys_np)
    vcol = colmod.from_numpy(vals_np)
    count = jnp.asarray(n, jnp.int32)

    def run():
        out, g = groupby_mod.hash_groupby(
            (kcol, vcol), count, (0,),
            ((1, groupby_mod.AggOp.NUNIQUE),))
        g = int(g)
        return g, np.asarray(out[0].data)[:g], np.asarray(out[1].data)[:g]

    a, b = _per_mode(realize, run)
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2])
    # pandas ground truth
    want = (pd.DataFrame({"k": keys_np[:n], "v": vals_np[:n]})
            .groupby("k")["v"].nunique())
    np.testing.assert_array_equal(a[2], want.to_numpy())


#: the table of ops/realization.py, written out: the TPU's row is the
#: configuration every line of PERF_LEDGER.jsonl was measured under
TABLE = {"tpu": {"permute": "sort", "scan": "pallas", "segsum": "pallas"},
         "host": {"permute": "scatter", "scan": "xla", "segsum": "scatter"}}


@pytest.mark.parametrize("field,accessor", [
    ("permute", compact.permute_mode), ("scan", segments.plain_scan_mode),
    ("segsum", segments.effective_mode)], ids=["permute", "scan", "segsum"])
@pytest.mark.parametrize("platform", ["tpu", "host"])
def test_realization_follows_the_platform(monkeypatch, platform, field,
                                          accessor):
    monkeypatch.setattr(precision, "on_tpu", lambda: platform == "tpu")
    assert realization.current()._asdict() == TABLE[platform]
    assert accessor() == TABLE[platform][field]


def _steered_kernels():
    """Per removed knob: the environment that used to select the other
    path, and the kernel it steered as an un-jitted function of small
    arrays."""
    mask = jnp.arange(64) % 3 == 0
    perm = jnp.arange(64, dtype=jnp.int32)[::-1]
    word = jnp.arange(64, dtype=jnp.int32)

    def lexsort():
        return keys.lexsort_indices([mask, word], 64, (word,))

    def segsum():
        from cylon_tpu.ops import groupby as groupby_mod

        gid = jnp.cumsum(mask.astype(jnp.int32))
        return groupby_mod._segment_aggregate(
            groupby_mod.AggOp.SUM, word.astype(jnp.float32), ~mask, gid, 64,
            0, spans=segments.segment_spans(mask.at[0].set(True)),
            boundaries=mask.at[0].set(True))

    radix = {"CYLON_TPU_SORT": "radix"}   # its two sub-knobs counted under it
    return {
        "CYLON_TPU_PERMUTE": ({"CYLON_TPU_PERMUTE": "sort"},
                              lambda: compact.compact_indices(mask)),
        "CYLON_TPU_INVPERM": ({"CYLON_TPU_INVPERM": "gather"},
                              lambda: compact.inverse_permute(perm, word)),
        "CYLON_TPU_SORT": (radix, lexsort),
        "CYLON_TPU_RADIX_BITS": ({**radix, "CYLON_TPU_RADIX_BITS": "4"},
                                 lexsort),
        "CYLON_TPU_RADIX_SCAN": ({**radix, "CYLON_TPU_RADIX_SCAN": "xla"},
                                 lexsort),
        "CYLON_TPU_SCAN": ({"CYLON_TPU_SCAN": "pallas"},
                           lambda: segments.run_extents(
                               mask, mask.at[0].set(True),
                               jnp.roll(mask, -1).at[-1].set(True))),
        "CYLON_TPU_SEGSUM": ({"CYLON_TPU_SEGSUM": "prefix"}, segsum),
    }


@pytest.mark.parametrize("name", [
    "CYLON_TPU_PERMUTE", "CYLON_TPU_INVPERM", "CYLON_TPU_SORT",
    "CYLON_TPU_RADIX_BITS", "CYLON_TPU_RADIX_SCAN", "CYLON_TPU_SCAN",
    "CYLON_TPU_SEGSUM"])
def test_removed_knob_is_gone(monkeypatch, name):
    """No operator can change how a kernel is realized: the name is
    unregistered, and setting the variables to what used to select the
    other path leaves the traced kernel as it was, on either platform."""
    assert name not in config.KNOBS
    with pytest.raises(KeyError):
        config.knob(name)
    old_env, kernel = _steered_kernels()[name]
    for on_tpu in (False, True):
        monkeypatch.setattr(precision, "on_tpu", lambda: on_tpu)
        for variable in old_env:
            monkeypatch.delenv(variable, raising=False)
        # a new function each time: make_jaxpr caches a trace by function
        before = str(jax.make_jaxpr(lambda: kernel())())
        for variable, value in old_env.items():
            monkeypatch.setenv(variable, value)
        assert str(jax.make_jaxpr(lambda: kernel())()) == before


@pytest.mark.parametrize("kg,algo", [(True, "sort"), (True, "hash"),
                                     (False, "sort"), (False, "hash")])
def test_join_projection_key_grouped_and_hash(kg, algo):
    """The production configuration (key_grouped + project, both
    algorithms): projected output must equal the full materialization's
    selected columns row-for-row (key_grouped order is pinned)."""
    rng = np.random.default_rng(11)
    cap = 1 << 9
    cols_l = (colmod.from_numpy(rng.integers(0, 60, cap).astype(np.int32)),
              colmod.from_numpy(rng.random(cap).astype(np.float32)))
    cols_r = (colmod.from_numpy(rng.integers(0, 60, cap).astype(np.int32)),
              colmod.from_numpy(rng.random(cap).astype(np.float32)))
    count = jnp.asarray(cap - 3, jnp.int32)

    full, n = join_mod.join_gather(cols_l, count, cols_r, count,
                                   (0,), (0,), JoinType.INNER, 1 << 12,
                                   algo, key_grouped=kg)
    proj, n2 = join_mod.join_gather(cols_l, count, cols_r, count,
                                    (0,), (0,), JoinType.INNER, 1 << 12,
                                    algo, key_grouped=kg,
                                    project=(0, 1, 3))
    n, n2 = int(n), int(n2)
    assert n == n2
    for want_idx, got in zip((0, 1, 3), proj):
        np.testing.assert_array_equal(np.asarray(full[want_idx].data)[:n],
                                      np.asarray(got.data)[:n])

    with pytest.raises(ValueError, match="project"):
        join_mod.join_gather(cols_l, count, cols_r, count, (0,), (0,),
                             JoinType.INNER, 1 << 12, algo,
                             project=(-1,))


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.FULL_OUTER])
def test_join_projection_pushdown(realize, jt):
    """project= must return exactly the selected columns of the full
    materialization, in the requested order, in both permute modes."""
    rng = np.random.default_rng(5)
    cap = 1 << 9
    cols_l = (colmod.from_numpy(rng.integers(0, 80, cap).astype(np.int32)),
              colmod.from_numpy(rng.random(cap).astype(np.float32)))
    cols_r = (colmod.from_numpy(rng.integers(0, 80, cap).astype(np.int32)),
              colmod.from_numpy(rng.random(cap).astype(np.float32)))
    count = jnp.asarray(cap - 7, jnp.int32)

    def run():
        full, n = join_mod.join_gather(cols_l, count, cols_r, count,
                                       (0,), (0,), jt, 1 << 12, "sort")
        proj, n2 = join_mod.join_gather(cols_l, count, cols_r, count,
                                        (0,), (0,), jt, 1 << 12, "sort",
                                        project=(3, 0, 1))
        n, n2 = int(n), int(n2)
        assert n == n2
        for want_idx, got in zip((3, 0, 1), proj):
            np.testing.assert_array_equal(
                np.asarray(full[want_idx].data)[:n],
                np.asarray(got.data)[:n])
            np.testing.assert_array_equal(
                np.asarray(full[want_idx].validity)[:n],
                np.asarray(got.validity)[:n])
        return n

    a, b = _per_mode(realize, run)
    assert a == b


def _parent_mask_sort_perm(mask):
    """PR 31's ``compact._mask_sort_perm`` below 2^31 rows."""
    cap = mask.shape[0]
    bits = compact.index_bits(cap)
    iota = jnp.arange(cap, dtype=jnp.uint32)
    word = (jnp.where(mask, jnp.uint32(0), jnp.uint32(1))
            << jnp.uint32(bits)) | iota
    s = jax.lax.sort(word, is_stable=False)
    return (s & jnp.uint32((1 << bits) - 1)).astype(jnp.int32)


def _parent_compact_indices(mask):
    """PR 31's ``compact_indices``: the index alone, the plain reference of
    the carrying form (``jnp.take(x, idx)`` is the rest of it)."""
    cap = mask.shape[0]
    new_count = jnp.sum(mask, dtype=jnp.int32)
    if compact.permute_mode() == "sort":
        return _parent_mask_sort_perm(mask), new_count
    iota = jnp.arange(cap, dtype=jnp.int32)
    pos = jnp.cumsum(mask, dtype=jnp.int32) - 1
    idx = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(mask, pos, cap)].set(iota, mode="drop")
    return idx, new_count


def _parent_partition_indices(mask):
    """PR 31's ``partition_indices``."""
    cap = mask.shape[0]
    nt = jnp.sum(mask, dtype=jnp.int32)
    if compact.permute_mode() == "sort":
        return _parent_mask_sort_perm(mask), nt
    iota = jnp.arange(cap, dtype=jnp.int32)
    ct = jnp.cumsum(mask, dtype=jnp.int32)
    cf = iota + 1 - ct
    dest = jnp.where(mask, ct - 1, nt + cf - 1)
    perm = jnp.zeros((cap,), jnp.int32).at[dest].set(iota)
    return perm, nt


PARENT = {"compact_indices": _parent_compact_indices,
          "partition_indices": _parent_partition_indices}


@contextlib.contextmanager
def index_then_take():
    """Run the body with the compactions as PR 31 had them: an index, and
    ``jnp.take`` through it for whatever has to move.  The whole-kernel
    reference of the join's and the group-by's carrying compactions."""
    from unittest import mock

    def plain(name):
        def fn(mask, *payload):
            idx, count = PARENT[name](mask)
            return (idx, count, *(jnp.take(x, idx) for x in payload))
        return fn

    jax.clear_caches()
    try:
        with mock.patch.object(compact, "compact_indices",
                               plain("compact_indices")), \
                mock.patch.object(compact, "partition_indices",
                                  plain("partition_indices")):
            yield
    finally:
        jax.clear_caches()


def _odd_floats(rng, cap):
    """float32 rows whose bits a value comparison would lose: NaNs with
    payload bits, -0.0 beside 0.0, infinities, denormals."""
    bits = rng.integers(0, 1 << 32, cap, dtype=np.uint64).astype(np.uint32)
    odd = np.array([0x7FC00001, 0xFFC12345, 0x7F800001, 0x80000000, 0,
                    0x7F800000, 0xFF800000, 1], np.uint32)
    return np.where(rng.random(cap) < 0.5, odd[rng.integers(0, 8, cap)],
                    bits).view(np.float32)


def _carried_arrays(rng, cap, kind):
    def words():
        return keys.pack_bits([jnp.asarray(rng.random(cap) < 0.5)
                               for _ in range(7)])[0]

    make = {
        "bits": words,
        "int32": lambda: rng.integers(-2 ** 31, 2 ** 31, cap).astype(np.int32),
        "uint32": lambda: rng.integers(0, 2 ** 32, cap).astype(np.uint32),
        "float32": lambda: _odd_floats(rng, cap),
    }
    kinds = [kind, kind] if kind in make else [
        "bits", "int32", "uint32", "float32", "float32"]
    return [jnp.asarray(make[k]()) for k in kinds]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("fill", ["mixed", "all_true", "all_false"])
@pytest.mark.parametrize("cap", [1, 8, 1000])
@pytest.mark.parametrize("kind", ["bits", "int32", "uint32", "float32",
                                  "five_arrays"])
def test_carrying_form_equals_take(realize, kind, cap, fill, mode):
    """``fn(mask, *payload)``: the index and the count of ``fn(mask)``, and
    each array as ``jnp.take(x, idx)`` returns it, bit for bit, filler
    and all, under both rows of ``ops/realization.py``."""
    rng = np.random.default_rng(cap)
    mask = jnp.asarray({"mixed": rng.random(cap) < 0.4,
                        "all_true": np.ones(cap, bool),
                        "all_false": np.zeros(cap, bool)}[fill])
    payload = _carried_arrays(rng, cap, kind)
    with realize(realization.current()._replace(permute=mode)):
        for fn in (compact.compact_indices, compact.partition_indices):
            want_idx, want_count = PARENT[fn.__name__](mask)
            idx, count, *carried = fn(mask, *payload)
            np.testing.assert_array_equal(idx, want_idx)
            assert int(count) == int(want_count) == int(mask.sum())
            assert len(carried) == len(payload)
            for x, moved in zip(payload, carried):
                want = np.asarray(jnp.take(x, want_idx))
                assert moved.dtype == x.dtype and moved.shape == x.shape
                assert np.asarray(moved).tobytes() == want.tobytes()
        if mode == "sort":
            jaxpr = str(jax.make_jaxpr(lambda m, *p: compact.compact_indices(
                m, *p))(mask, *payload))
            assert " gather[" not in jaxpr and jaxpr.count(" sort[") == 1


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", list(PARENT))
def test_without_payload_the_jaxpr_is_the_parents(realize, name, mode):
    """Every other caller (``unique``, the set operations, the filters, the
    exchange's partitions) traces what it traced before."""
    mask = jnp.arange(1000) % 3 == 0
    with realize(realization.current()._replace(permute=mode)):
        got = jax.make_jaxpr(lambda m: getattr(compact, name)(m))(mask)
        want = jax.make_jaxpr(lambda m: PARENT[name](m))(mask)
        assert str(got) == str(want)
        assert len(got.out_avals) == 2


def test_two_words_past_2_31_rows_carry_too():
    """The stable two-operand sort of a mask of more than 2^31 rows carries
    the payload beside the int64 index (traced, never run)."""
    cap = (1 << 31) + 8
    jaxpr = jax.make_jaxpr(lambda m, x: compact._mask_sort_perm(m, (x,)))(
        jax.ShapeDtypeStruct((cap,), jnp.bool_),
        jax.ShapeDtypeStruct((cap,), jnp.float32))
    sort, = [e for e in jaxpr.eqns if e.primitive.name == "sort"]
    assert sort.params["num_keys"] == 1 and sort.params["is_stable"]
    assert [v.aval.dtype for v in sort.invars] == [
        jnp.uint32, jnp.int64, jnp.float32]
    assert [a.dtype for a in jaxpr.out_avals] == [jnp.int64, jnp.float32]


def test_counter_says_how_many_lanes_rode_a_compaction(realize):
    from cylon_tpu.obs import metrics

    mask = jnp.arange(64) % 3 == 0
    payload = (jnp.arange(64, dtype=jnp.int32),
               jnp.arange(64, dtype=jnp.float64),
               jnp.arange(64, dtype=jnp.uint32))
    with realize(realization.current()._replace(permute="sort")):
        before = metrics.counter_value("compact.payload_lanes")
        jax.make_jaxpr(lambda m, *p: compact.compact_indices(m, *p))(
            mask, *payload)
        assert metrics.counter_value("compact.payload_lanes") - before == 4
        jax.make_jaxpr(lambda m: compact.partition_indices(m))(mask)
        assert metrics.counter_value("compact.payload_lanes") - before == 4
    assert "compact.payload_lanes" in metrics.snapshot()["counters"]


def _compacted_columns(rng, case):
    """(mask, columns) of one case of ``keys.compact_columns``; the
    columns are built buffer by buffer, so that a null keeps whatever
    bits lie under it."""
    from cylon_tpu import dtypes

    cap = 777 if case == "odd_capacity" else 1024

    def column(data, logical, p_valid=0.8):
        return colmod.Column(jnp.asarray(data),
                             jnp.asarray(rng.random(cap) < p_valid), None,
                             logical)

    def int32():
        return column(rng.integers(-2 ** 31, 2 ** 31, cap).astype(np.int32),
                      dtypes.int32)

    def float64():
        bits = rng.integers(0, 1 << 63, cap, dtype=np.uint64)
        odd = np.array([0x7FF8000000000001, 0xFFF8000000012345,
                        0x8000000000000000, 0, 0x7FF0000000000000, 1],
                       np.uint64)
        return column(np.where(rng.random(cap) < 0.5,
                               odd[rng.integers(0, 6, cap)],
                               bits).view(np.float64), dtypes.double)

    def int64():
        return column(rng.integers(-2 ** 63, 2 ** 63, cap), dtypes.int64)

    def string():
        words = np.array(["", "a", "bravo", "charlie delta"], object)
        return colmod.from_numpy(words[rng.integers(0, 4, cap)],
                                 validity=rng.random(cap) < 0.8)

    cols = {
        "int32_nulls": lambda: [int32()],
        "float64_odd_bits": lambda: [float64(), float64()],
        "int64": lambda: [int64(), int32()],
        "string_beside_numeric": lambda: [int32(), string(), float64()],
        "past_the_lane_budget": lambda: [int32() for _ in range(9)]
        + [float64(), int64(), int32()],
    }.get(case, lambda: [int32(), float64()])()
    mask = {"all_false": np.zeros(cap, bool),
            "all_true": np.ones(cap, bool)}.get(case, rng.random(cap) < 0.4)
    return jnp.asarray(mask), cols


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", [
    "int32_nulls", "float64_odd_bits", "int64", "string_beside_numeric",
    "past_the_lane_budget", "all_false", "all_true", "odd_capacity"])
def test_compact_columns_equals_index_then_take(realize, case, mode):
    """``keys.compact_columns``: what the index of ``compact_indices(mask)``
    and ``Column.take(idx, valid_mask=live)`` of every column gave the
    filters before, buffer by buffer and bit for bit, nulls and filler
    included, under both rows of ``ops/realization.py``; under ``sort`` only
    a buffer that cannot ride goes through an index."""
    mask, cols = _compacted_columns(np.random.default_rng(len(case)), case)
    cap = mask.shape[0]
    with realize(realization.current()._replace(permute=mode)):
        idx, want_count = _parent_compact_indices(mask)
        live = compact.live_mask(cap, want_count)
        want = [c.take(idx, valid_mask=live) for c in cols]
        got, count = keys.compact_columns(mask, cols)
        jaxpr = str(jax.make_jaxpr(
            lambda m, c: keys.compact_columns(m, c))(mask, cols))
    assert int(count) == int(want_count) == int(mask.sum())
    assert isinstance(got, tuple) and len(got) == len(cols)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        for moved, taken in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            assert moved.dtype == taken.dtype and moved.shape == taken.shape
            assert np.asarray(moved).tobytes() == np.asarray(taken).tobytes()
    if mode == "sort":
        # 32-bit lanes past the word of validity: 9 + 2 ride, the int64
        # and the last int32 do not; a string's bytes never do
        took = {"string_beside_numeric": 1, "past_the_lane_budget": 2}
        assert jaxpr.count(" sort[") == 1
        assert jaxpr.count(" gather[") == took.get(case, 0)


def _frame(rng, n):
    a = rng.integers(0, 50, n).astype(np.int32)
    x = rng.random(n)
    x[rng.random(n) < 0.2] = np.nan                 # nulls
    s = np.array(["ash", "birch", None, "cedar"], object)[
        rng.integers(0, 4, n)]
    return pd.DataFrame({"a": a, "x": x, "s": s,
                         "big": rng.integers(-2 ** 62, 2 ** 62, n)})


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("op", ["select", "filter", "merge", "planned"])
def test_row_compactions_match_pandas(realize, op, mode):
    """``Table.select``, ``Table.filter``, ``Table.merge`` and a planned
    filter, whose compactions all go through ``keys.compact_columns``,
    end to end against pandas under both rows."""
    from cylon_tpu import CylonContext, Table
    from cylon_tpu.plan import col

    rng = np.random.default_rng(34)
    df, other = _frame(rng, 300), _frame(rng, 41)
    with realize(realization.current()._replace(permute=mode)):
        ctx = CylonContext.Init()
        t = Table.from_pandas(df, ctx=ctx)
        if op == "select":
            got, want = t.select(lambda r: r.a % 3 == 1), df[df.a % 3 == 1]
        elif op == "filter":
            keep = Table.from_pandas(pd.DataFrame({"m": df.a.values > 20}),
                                     ctx=ctx)
            got, want = t.filter(keep), df[df.a > 20]
        elif op == "merge":
            got = t.merge(Table.from_pandas(other, ctx=ctx))
            want = pd.concat([df, other])
        else:
            got = t.plan().filter((col("a") >= 10) & (col("x") < 0.5)
                                  ).execute()
            want = df[(df.a >= 10) & (df.x < 0.5)]
        got = got.to_pandas()
    assert len(want) and list(got.columns) == list(want.columns)
    pd.testing.assert_frame_equal(
        got.reset_index(drop=True),
        want.reset_index(drop=True).astype(got.dtypes.to_dict()),
        check_exact=True)


def test_a_filter_counts_the_lanes_that_ride_and_the_lanes_it_takes(realize):
    """TPC-H Q5's filter of the orders keeps two int32 columns: two data
    lanes and one word of validity ride the compaction's sort and nothing
    is taken; a string column's byte matrix is (its lengths ride)."""
    from cylon_tpu import CylonContext, Table
    from cylon_tpu.obs import metrics
    from cylon_tpu.plan import col

    names = ("compact.payload_lanes", "sort.payload_lanes", "sort.take_lanes")

    def counted(run):
        before = [metrics.counter_value(n) for n in names]
        run()
        return [metrics.counter_value(n) - b for n, b in zip(names, before)]

    with realize(realization.current()._replace(permute="sort")):
        ctx = CylonContext.Init()
        orders = Table.from_pydict({n: np.arange(64, dtype=np.int32) for n in (
            "o_orderkey", "o_custkey", "o_orderdate")}, ctx=ctx)
        dates = (col("o_orderdate") >= 7) & (col("o_orderdate") < 30)
        assert counted(orders.plan().filter(dates).project(
            ["o_orderkey", "o_custkey"]).execute) == [3, 3, 0]
        tagged = Table.from_pydict({"k": np.arange(64, dtype=np.int32),
                                    "tag": ["ab", "c"] * 32}, ctx=ctx)
        width = tagged.columns[1].data.shape[1]
        assert counted(lambda: tagged.select(lambda r: r.k % 2 == 0)) == [
            3, 3, -(-width // 4)]


def _targets_with_strays(rng, cap, world):
    """int32[cap] targets in [0, world], padding (== world) included, with
    negative and past-``world`` values that ``_remap_oob_targets`` sends to
    padding."""
    t = rng.integers(0, world + 1, cap)
    stray = rng.random(cap) < 0.1
    t = np.where(stray, rng.choice([-1, -7, world + 1, world + 40], cap), t)
    return jnp.asarray(t.astype(np.int32))


def _plane_words(rng, cap, n):
    """``n`` uint32 words a plane could hold: float32 bit patterns a value
    comparison would lose (NaN payloads, -0.0), all-ones, and random bits."""
    words = [jax.lax.bitcast_convert_type(jnp.asarray(_odd_floats(rng, cap)),
                                          jnp.uint32),
             jnp.full((cap,), 0xFFFFFFFF, jnp.uint32)]
    while len(words) < n:
        words.append(jnp.asarray(
            rng.integers(0, 1 << 32, cap, dtype=np.uint64).astype(np.uint32)))
    return words[:n]


def _sorts(jaxpr):
    return [e for e in jaxpr.eqns if e.primitive.name == "sort"]


def _stable_by_target(targets, world):
    """numpy's stable grouping of ``targets`` by value, strays (negative,
    past ``world``) with the padding: the permutation to agree with."""
    t = np.asarray(targets)
    t = np.where((t < 0) | (t > world), world, t)
    return np.argsort(t, kind="stable"), t


@pytest.mark.parametrize("cap", [1, 2, 1023, 1025])
@pytest.mark.parametrize("world", [1, 2, 4, 8, 40])
def test_perm_by_target_carries_its_payload(realize, world, cap):
    """``_perm_by_target(targets, world, *payload)`` on either row: the
    stable grouping by target, padding and strays last, and each payload
    word as ``jnp.take`` through it returns it, bit for bit.  On the TPU's
    row it is ``compact.sort_by_target``, one single-key sort of the packed
    word with the payload behind it; on the ``scatter`` row the counting
    scan and a take (world 40 is past the scan's wide-mesh cutoff and sorts
    on both rows; its target still fits the word)."""
    from cylon_tpu.parallel import shuffle

    rng = np.random.default_rng(world * 7919 + cap)
    targets = _targets_with_strays(rng, cap, world)
    payload = _plane_words(rng, cap, 3)
    want, remapped = _stable_by_target(targets, world)
    for mode in MODES:
        with realize(realization.current()._replace(permute=mode)):
            perm, *carried = shuffle._perm_by_target(targets, world,
                                                     *payload)
            jaxpr = jax.make_jaxpr(lambda t, *p: shuffle._perm_by_target(
                t, world, *p))(targets, *payload)
        np.testing.assert_array_equal(perm, want)
        assert perm.dtype == jnp.int32
        assert (np.diff(remapped[np.asarray(perm)]) >= 0).all()
        for word, moved in zip(payload, carried):
            assert moved.dtype == word.dtype and moved.shape == word.shape
            assert np.asarray(moved).tobytes() == np.asarray(word)[
                want].tobytes()
        sorts = _sorts(jaxpr)
        if mode == "scatter" and world < 40:
            assert not sorts
            continue
        sort, = sorts
        assert sort.params["num_keys"] == 1 and not sort.params["is_stable"]
        assert [v.aval.dtype for v in sort.invars] == [jnp.uint32] * 4
        assert " gather[" not in str(jaxpr)


@pytest.mark.parametrize("case", ["wide_alphabet", "past_2_31_rows"])
def test_sort_by_target_falls_back_past_32_bits(realize, case):
    """Where target and row index do not fit one word, the two-operand
    stable sort carries the payload: run at 2^13 rows to 2^20 targets (21 +
    13 bits), traced at more than 2^31 rows (int64 index)."""
    from cylon_tpu.parallel import shuffle

    if case == "past_2_31_rows":
        cap, world = (1 << 31) + 8, 4
        jaxpr = jax.make_jaxpr(lambda t, x: compact.sort_by_target(t, world, x))(
            jax.ShapeDtypeStruct((cap,), jnp.int32),
            jax.ShapeDtypeStruct((cap,), jnp.uint32))
        index = jnp.int64
    else:
        cap, world = 1 << 13, 1 << 20
        rng = np.random.default_rng(13)
        targets = _targets_with_strays(rng, cap, world)
        payload = _plane_words(rng, cap, 2)
        want, _ = _stable_by_target(targets, world)
        with realize(realization.ON_TPU):
            perm, *carried = shuffle._perm_by_target(targets, world, *payload)
            jaxpr = jax.make_jaxpr(lambda t, *p: shuffle._perm_by_target(
                t, world, *p))(targets, *payload)
        np.testing.assert_array_equal(perm, want)
        for word, moved in zip(payload, carried):
            assert np.asarray(moved).tobytes() == np.asarray(word)[
                want].tobytes()
        index = jnp.int32
    sort, = _sorts(jaxpr)
    assert sort.params["num_keys"] == 1 and sort.params["is_stable"]
    assert [v.aval.dtype for v in sort.invars][:2] == [jnp.int32, index]
    assert jaxpr.out_avals[0].dtype == index


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("nwords", [1, 3, compact.MAX_PAYLOAD_LANES, 15])
def test_plane_by_target_equals_the_gathered_plane(realize, nwords, mode):
    """The exchange's plane grouped by target: the stacked words in the
    stable order by target, bit for bit.  Under
    ``sort`` the words ride the target sort up to the payload a sort
    carries and only the rest is gathered; under ``scatter`` the plane is
    gathered as before."""
    from cylon_tpu.parallel import shuffle

    world, cap = 4, 1000
    rng = np.random.default_rng(nwords)
    targets = _targets_with_strays(rng, cap, world)
    words = _plane_words(rng, cap, nwords)
    order = _stable_by_target(targets, world)[0]
    want = np.stack(words, axis=1)[order]
    with realize(realization.current()._replace(permute=mode)):
        perm, got = shuffle._plane_by_target(targets, world, words)
        jaxpr = jax.make_jaxpr(lambda t, *w: shuffle._plane_by_target(
            t, world, list(w))[1])(targets, *words)
        ride = shuffle.riding_words(nwords)
    np.testing.assert_array_equal(np.asarray(perm), order)
    assert got.shape == (cap, nwords) and got.dtype == jnp.uint32
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert ride == (min(nwords, compact.MAX_PAYLOAD_LANES) if mode == "sort"
                    else 0)
    gathers = str(jaxpr).count(" gather[")
    assert gathers == (1 if ride < nwords else 0)
    if mode == "sort":
        sort, = _sorts(jaxpr)
        assert len(sort.invars) == 1 + ride


@pytest.mark.parametrize("cap", [1, 1000, 1 << 20])
def test_mask_sort_perm_traces_the_parents_program(cap):
    """The new small-alphabet form leaves the mask's sort as it was."""
    mask = jax.ShapeDtypeStruct((cap,), jnp.bool_)
    got = jax.make_jaxpr(lambda m: compact._mask_sort_perm(m))(mask)
    want = jax.make_jaxpr(lambda m: _parent_mask_sort_perm(m))(mask)
    assert str(got) == str(want)


@pytest.mark.parametrize("case", [
    ("sort", "ragged", True, 12, 3), ("scatter", "ragged", True, 0, 15),
    ("sort", "bucketed", True, 0, 15), ("sort", "ragged", False, 0, 0),
    ("sort", "cell_join", True, 3, 0)],
    ids=["sort_ragged", "scatter_ragged", "sort_bucketed", "perbuf",
         "cell_join"])
def test_an_exchange_counts_its_riding_and_taken_words(realize, case):
    """``_record_exchange`` adds, once an exchange that ran, the packed
    plane's words that rode the ragged exchange's target sort to
    ``shuffle.payload_lanes`` and the rest to ``shuffle.take_lanes``: 14
    int32 columns are 15 words (the validity bits share one), the
    benchmark's join (a narrowed int64 key, a float64) 3."""
    from cylon_tpu import dtypes
    from cylon_tpu.obs import metrics
    from cylon_tpu.parallel import ops as par_ops, plane

    mode, family, packed, rode, taken = case
    n = 64

    def column(dt, logical):
        return colmod.Column(jnp.zeros((n,), dt), jnp.ones((n,), bool), None,
                             logical)

    spec = None
    if family == "cell_join":
        family = "ragged"
        cols = (column(jnp.int64, dtypes.int64),
                column(jnp.float64, dtypes.double))
        spec = (("narrow", 0, 26), plane.RAW)
    else:
        cols = tuple(column(jnp.int32, dtypes.int32) for _ in range(14))
    names = ("shuffle.payload_lanes", "shuffle.take_lanes")
    with realize(realization.current()._replace(permute=mode)):
        before = [metrics.counter_value(name) for name in names]
        par_ops._record_exchange(cols, packed, family, 10, spec=spec)
        grew = [metrics.counter_value(name) - b
                for name, b in zip(names, before)]
    assert grew == [rode, taken]
    if packed:
        assert rode + taken == plane.plane_words(cols, spec)
