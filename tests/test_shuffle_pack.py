"""Packed-plane vs per-buffer shuffle exchange must agree bit-for-bit.

``CYLON_TPU_SHUFFLE_PACK`` selects how the shuffle moves a table across
the mesh: one bit-packed u32 plane through ONE collective
(parallel/plane.py — the TPU default, where collective launch count
dominates), or one collective per buffer per column (the original
realization, still the CPU default).  The exchange is the framework's
central primitive (reference: cpp/src/cylon/arrow/arrow_all_to_all.cpp:
24-236), so both realizations are pinned against each other on every
covered shape — the dual-realization discipline of
tests/test_permute_modes.py applied to the collective plane — and the
collective-launch reduction itself is asserted by jaxpr inspection.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from cylon_tpu import column as colmod
from cylon_tpu import dtypes, precision
from cylon_tpu.column import Column
from cylon_tpu.ops import realization
from cylon_tpu.parallel import plane, shuffle as shuffle_mod

PACK_MODES = ("0", "1")
PERMUTE_MODES = ("scatter", "sort")


# ---------------------------------------------------------------------------
# plane round trip
# ---------------------------------------------------------------------------

def _mixed_columns(cap: int, rng) -> tuple:
    """One column of every physical layout: 64/32/16/8-bit ints, floats of
    all three widths (with NaN / -0.0 payloads), bool, strings with nulls
    and empty values."""
    f32 = rng.random(cap).astype(np.float32)
    f32[0] = np.nan
    f32[1 % cap] = -0.0
    words = np.array(["alpha", None, "", "z" * 37, "beta"], object)
    return (
        colmod.from_numpy(rng.integers(-2**62, 2**62, cap).astype(np.int64)),
        colmod.from_numpy(rng.integers(0, 2**32, cap).astype(np.uint32)),
        colmod.from_numpy(rng.integers(-2**15, 2**15, cap).astype(np.int16)),
        colmod.from_numpy(rng.integers(0, 2**8, cap).astype(np.uint8)),
        colmod.from_numpy(f32),
        colmod.from_numpy(rng.random(cap).astype(np.float64)),
        colmod.from_numpy(rng.random(cap).astype(np.float16)),
        colmod.from_numpy(rng.integers(0, 2, cap).astype(bool)),
        colmod.from_numpy(words[rng.integers(0, 5, cap)]),
    )


def _assert_cols_equal(a, b, ctx=""):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.data.dtype == y.data.dtype, (ctx, i)
        np.testing.assert_array_equal(
            np.asarray(x.data), np.asarray(y.data), err_msg=f"{ctx} col {i}")
        np.testing.assert_array_equal(
            np.asarray(x.validity), np.asarray(y.validity),
            err_msg=f"{ctx} col {i} validity")
        assert (x.lengths is None) == (y.lengths is None)
        if x.lengths is not None:
            np.testing.assert_array_equal(
                np.asarray(x.lengths), np.asarray(y.lengths),
                err_msg=f"{ctx} col {i} lengths")


@pytest.mark.parametrize("cap", [1, 7, 256])
def test_plane_roundtrip_all_dtypes(cap, rng):
    cols = _mixed_columns(cap, rng)
    packed = plane.pack_plane(cols)
    assert packed.dtype == jnp.uint32
    # from_numpy pads capacity to >= 8; the plane covers the full capacity
    assert packed.shape == (cols[0].capacity, plane.plane_words(cols))
    out = plane.unpack_plane(packed, cols)
    # float payloads travel as raw bits, so even NaN is preserved exactly:
    # compare bit patterns, not values
    bits_a = np.asarray(cols[4].data).view(np.uint32)
    bits_b = np.asarray(out[4].data).view(np.uint32)
    np.testing.assert_array_equal(bits_a, bits_b)
    _assert_cols_equal(cols, out, "roundtrip")


def test_plane_carries_a_float64_as_the_float32_pair_a_tpu_holds(
        monkeypatch, rng):
    """On a TPU a float64 is a pair of float32 and has no bitcast: the
    plane carries the two halves.  Every value such a pair can hold comes
    back bit for bit, -0.0, the infinities and NaN included, and the zero
    rows of an unwritten tail decode to 0.0."""
    head = rng.standard_normal(256).astype(np.float32)
    rest = (head * rng.uniform(-2.0 ** -25, 2.0 ** -25, 256)).astype(
        np.float32)
    values = head.astype(np.float64) + rest.astype(np.float64)
    values[:6] = [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0]
    cols = (colmod.from_numpy(np.arange(256, dtype=np.int64) * -(2 ** 40)),
            Column(jnp.asarray(values), jnp.ones(256, bool), None,
                   dtypes.double))
    exact = plane.unpack_plane(plane.pack_plane(cols), cols)
    monkeypatch.setattr(precision, "on_tpu", lambda: True)
    packed = plane.pack_plane(cols)
    assert packed.shape == (256, plane.plane_words(cols))
    out = plane.unpack_plane(packed, cols)
    np.testing.assert_array_equal(
        np.asarray(out[1].data).view(np.uint64), values.view(np.uint64))
    _assert_cols_equal(out, exact, "float32 pair")
    tail = plane.unpack_plane(jnp.zeros_like(packed), cols)
    assert not np.asarray(tail[1].data).view(np.uint64).any()


def test_plane_valid_mask_zeroes_tail(rng):
    cap = 64
    cols = _mixed_columns(cap, rng)
    packed = plane.pack_plane(cols)
    mask = jnp.arange(cap, dtype=jnp.int32) < 10
    out = plane.unpack_plane(packed, cols, valid_mask=mask)
    for c in out:
        assert not np.asarray(c.validity)[10:].any()
        assert (np.asarray(c.data)[10:] == 0).all()
        if c.lengths is not None:
            assert (np.asarray(c.lengths)[10:] == 0).all()


def test_plane_preserves_null_rows_raw_bits():
    """Unmasked decode must reproduce null rows' buffers EXACTLY — the
    ragged exchange's per-buffer realization moves raw bytes with no
    masking (a from_native_buffers null row can carry nonzero data), so
    the packed ragged path decodes without a mask and must round-trip
    those bits untouched."""
    import jax.numpy as jnp

    from cylon_tpu import dtypes
    from cylon_tpu.column import Column

    n = 16
    data = jnp.arange(1, n + 1, dtype=jnp.int64) * jnp.int64(-7)
    validity = jnp.asarray((np.arange(n) % 3) != 0)
    smat = jnp.asarray((np.arange(n * 8) % 251 + 1).reshape(n, 8),
                       dtype=jnp.uint8)
    slen = jnp.full((n,), 8, jnp.int32)
    cols = (Column(data, validity, None, dtypes.int64),
            Column(smat, validity, slen, dtypes.string))
    out = plane.unpack_plane(plane.pack_plane(cols), cols)
    _assert_cols_equal(cols, out, "null-rows-raw")
    # the junk on validity=False rows really is nonzero — the test bites
    assert (np.asarray(out[0].data)[~np.asarray(validity)] != 0).all()


def test_plane_word_count_is_dense(rng):
    """First-fit-decreasing packing: a narrow 10-column i32 table must
    travel as 11 words (10 data + 1 word of validity bits), not 20."""
    cols = tuple(colmod.from_numpy(rng.integers(0, 100, 32).astype(np.int32))
                 for _ in range(10))
    assert plane.plane_words(cols) == 11


def test_pack_enabled_default_by_backend(monkeypatch):
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    want = precision.on_tpu()
    assert plane.pack_enabled() == want
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", "1")
    assert plane.pack_enabled()
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", "0")
    assert not plane.pack_enabled()


# ---------------------------------------------------------------------------
# packed vs per-buffer exchange: bit-identical shard contents
# ---------------------------------------------------------------------------

def _table(ctx, df, rng_unused=None):
    from cylon_tpu.table import Table

    return Table.from_pandas(df, ctx=ctx)


def _shard_frames(t):
    """Per-shard host frames, bit-exact (raw column buffers, not pandas)."""
    out = []
    for sid, scols, cnt in t._addressable_host_shards():
        frame = {}
        for name, c in zip(t.names, scols):
            frame[name] = (np.asarray(c.data)[:cnt],
                           np.asarray(c.validity)[:cnt],
                           None if c.lengths is None
                           else np.asarray(c.lengths)[:cnt])
        out.append((sid, cnt, frame))
    return out


def _assert_shards_equal(a, b):
    assert len(a) == len(b)
    for (sid0, c0, f0), (sid1, c1, f1) in zip(a, b):
        assert sid0 == sid1 and c0 == c1
        for name in f0:
            for x, y in zip(f0[name], f1[name]):
                if x is None:
                    assert y is None
                else:
                    np.testing.assert_array_equal(x, y,
                                                  err_msg=f"shard {sid0} "
                                                          f"{name}")


def _mixed_df(n, rng, keys=50):
    words = np.array(["alpha", "beta", None, "g" * 40, ""], object)
    return pd.DataFrame({
        "k": rng.integers(0, keys, n).astype(np.int64),
        "v": rng.random(n).astype(np.float32),
        "w": rng.random(n).astype(np.float64),
        "b": rng.integers(0, 2, n).astype(bool),
        "i8": rng.integers(-100, 100, n).astype(np.int8),
        "s": words[rng.integers(0, 5, n)],
    })


def _ab_shuffle(monkeypatch, t, keys):
    shards = {}
    for mode in PACK_MODES:
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", mode)
        s = t.shuffle(keys)
        shards[mode] = (s.row_count, _shard_frames(s))
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    assert shards["0"][0] == shards["1"][0]
    _assert_shards_equal(shards["0"][1], shards["1"][1])
    return shards["0"][0]


@pytest.mark.parametrize("world_fixture", ["local_ctx", "ctx2", "ctx4",
                                           "ctx8"])
@pytest.mark.parametrize("permute", PERMUTE_MODES)
def test_packed_vs_perbuffer_worlds(world_fixture, permute, monkeypatch,
                                    realize, rng, request):
    ctx = request.getfixturevalue(world_fixture)
    n = 2000
    df = _mixed_df(n, rng)
    with realize(realization.current()._replace(permute=permute)):
        assert _ab_shuffle(monkeypatch, _table(ctx, df), ["k"]) == n


@pytest.mark.parametrize("world_fixture", ["ctx4", "ctx8"])
def test_packed_vs_perbuffer_skewed(world_fixture, monkeypatch, rng,
                                    request):
    """One hot key: all rows land on one shard, the rest get EMPTY buckets
    — the shape the bucketed plan over-pads and the plane must survive."""
    ctx = request.getfixturevalue(world_fixture)
    n = 1500
    df = _mixed_df(n, rng)
    df["k"] = np.int64(7)
    total = _ab_shuffle(monkeypatch, _table(ctx, df), ["k"])
    assert total == n


def test_packed_vs_perbuffer_tiny_and_empty(ctx8, monkeypatch, rng):
    """Fewer rows than shards, and a zero-row table."""
    df = _mixed_df(3, rng)
    assert _ab_shuffle(monkeypatch, _table(ctx8, df), ["k"]) == 3
    empty = _mixed_df(0, rng)
    assert _ab_shuffle(monkeypatch, _table(ctx8, empty), ["k"]) == 0


def test_packed_hash_partition_agrees(ctx4, monkeypatch, rng):
    """hash_partition's packed split (one plane gather per partition) must
    match the per-column realization on every partition."""
    df = _mixed_df(800, rng)
    t = _table(ctx4, df)
    parts = {}
    for mode in PACK_MODES:
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", mode)
        parts[mode] = t.hash_partition(["k"], 3)
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    assert parts["0"].keys() == parts["1"].keys()
    for p in parts["0"]:
        a, b = parts["0"][p], parts["1"][p]
        assert a.row_count == b.row_count
        _assert_shards_equal(_shard_frames(a), _shard_frames(b))


def test_packed_task_shuffle_agrees(ctx4, monkeypatch, rng):
    from cylon_tpu.parallel.task import LogicalTaskPlan, task_shuffle

    plan = LogicalTaskPlan({0: 1, 1: 3, 2: 0}, 4)
    tables = [_table(ctx4, pd.DataFrame({
        "a": rng.integers(0, 100, 200).astype(np.int64),
        "x": rng.random(200).astype(np.float32)})) for _ in range(3)]
    outs = {}
    for mode in PACK_MODES:
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", mode)
        outs[mode] = task_shuffle(tables, [0, 1, 2], plan)
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    for a, b in zip(outs["0"], outs["1"]):
        assert a.row_count == b.row_count == 200
        _assert_shards_equal(_shard_frames(a), _shard_frames(b))


# ---------------------------------------------------------------------------
# the launch-count claim itself: >= O(buffers x columns) -> <= 2
# ---------------------------------------------------------------------------

_EXCHANGE_PRIMS = ("all_to_all", "ragged_all_to_all")
_COUNT_PRIMS = ("all_gather",)

# the shared jaxpr meter — single-sourced with the committed collective
# budgets (cylon_tpu/analysis/budgets/*.json) so this test and the cylint
# budget gate can never disagree on what counts as a launch
from cylon_tpu.analysis.budgets import count_prims as _count_prims  # noqa: E402


def _traced_shuffle(ctx, cols, targets, world, bucket, out_cap):
    from jax.sharding import PartitionSpec as P

    from cylon_tpu.context import PARTITION_AXIS
    from cylon_tpu.utils import shard_map

    def fn(cc, tgt):
        out_cols, total = shuffle_mod.shuffle_shard(
            cc, None, tgt, world, bucket, out_cap)
        return out_cols, jnp.reshape(total, (1,))

    f = jax.jit(shard_map(fn, mesh=ctx.mesh, in_specs=P(PARTITION_AXIS),
                          out_specs=P(PARTITION_AXIS), check_vma=False))
    return jax.make_jaxpr(f)(cols, targets)


def test_collective_launch_count(ctx4, monkeypatch, rng):
    """The acceptance meter: the packed exchange runs ONE data collective
    (plus the count-matrix all_gather) regardless of column count, where
    the per-buffer exchange pays one per buffer per column."""
    world = 4
    shard_cap = 64
    n = world * shard_cap
    df = _mixed_df(n, rng)
    cols = tuple(colmod.from_numpy(df[c].to_numpy(), capacity=n)
                 for c in df.columns)
    targets = jnp.asarray(rng.integers(0, world, n).astype(np.int32))
    counts = {}
    for mode in PACK_MODES:
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", mode)
        jaxpr = _traced_shuffle(ctx4, cols, targets, world, shard_cap,
                                shard_cap * world)
        counts[mode] = (_count_prims(jaxpr.jaxpr, _EXCHANGE_PRIMS),
                        _count_prims(jaxpr.jaxpr, _COUNT_PRIMS))
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    # 6 columns: 6 data + 6 validity + 1 lengths = 13 per-buffer collectives
    assert counts["0"][0] == 13
    # packed: ONE data collective; with the count-matrix all_gather the
    # whole exchange is <= 2 collectives, independent of column count
    assert counts["1"][0] == 1
    assert counts["1"][0] + counts["1"][1] <= 2


def test_collective_launch_count_ragged(ctx4, monkeypatch, rng):
    """Same meter for the ragged body (trace-only: XLA:CPU cannot run it)."""
    from jax.sharding import PartitionSpec as P

    from cylon_tpu.context import PARTITION_AXIS
    from cylon_tpu.utils import shard_map

    world = 4
    shard_cap = 64
    n = world * shard_cap
    df = _mixed_df(n, rng)
    cols = tuple(colmod.from_numpy(df[c].to_numpy(), capacity=n)
                 for c in df.columns)
    targets = jnp.asarray(rng.integers(0, world, n).astype(np.int32))

    def fn(cc, tgt):
        out_cols, total = shuffle_mod.shuffle_shard_ragged(
            cc, tgt, world, shard_cap * world)
        return out_cols, jnp.reshape(total, (1,))

    counts = {}
    for mode in PACK_MODES:
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", mode)
        f = jax.jit(shard_map(fn, mesh=ctx4.mesh, in_specs=P(PARTITION_AXIS),
                              out_specs=P(PARTITION_AXIS), check_vma=False))
        jaxpr = jax.make_jaxpr(f)(cols, targets)
        counts[mode] = _count_prims(jaxpr.jaxpr, _EXCHANGE_PRIMS)
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    assert counts["0"] == 13
    assert counts["1"] == 1


# ---------------------------------------------------------------------------
# compressed payloads (ISSUE-10): bit-width reduction + dictionary codes
# on the packed plane must stay bit-identical to BOTH uncompressed
# realizations, and the bytes actually drop
# ---------------------------------------------------------------------------


def _edge_df(n, rng):
    """The compression edge grid: extreme 64-bit ranges (cannot narrow),
    negative ranges, a single-value column, an all-null float column,
    empty strings, and a low-cardinality category column."""
    cats = np.array(["AA", "B", "CCC"], object)
    return pd.DataFrame({
        "k": rng.integers(-20, 20, n).astype(np.int64),
        "ext": np.where(rng.integers(0, 2, n) == 0,
                        np.iinfo(np.int64).min,
                        np.iinfo(np.int64).max).astype(np.int64),
        "neg": rng.integers(-5000, -4000, n).astype(np.int64),
        "one": np.full(n, 42, np.int32),
        "nul": np.full(n, np.nan, np.float64),
        "empty_s": np.array([""] * n, object),
        "cat": cats[rng.integers(0, 3, n)],
        "ts": (rng.integers(0, 1000, n) + 1_600_000_000_000).astype(np.int64),
    })


def _abc_shuffle(monkeypatch, t, keys):
    """Three-arm A/B/C: per-buffer baseline, packed uncompressed, packed
    compressed — all three must agree bit-for-bit."""
    arms = {"perbuf": ("0", "0"), "packed": ("1", "0"), "comp": ("1", "1")}
    shards = {}
    for label, (pack, comp) in arms.items():
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", pack)
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_COMPRESS", comp)
        s = t.shuffle(keys)
        shards[label] = (s.row_count, _shard_frames(s))
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_COMPRESS", raising=False)
    assert shards["perbuf"][0] == shards["packed"][0] == shards["comp"][0]
    _assert_shards_equal(shards["perbuf"][1], shards["comp"][1])
    _assert_shards_equal(shards["packed"][1], shards["comp"][1])
    return shards["comp"][0]


@pytest.mark.parametrize("world_fixture", ["local_ctx", "ctx2", "ctx4"])
@pytest.mark.parametrize("permute", PERMUTE_MODES)
def test_compressed_vs_uncompressed_worlds(world_fixture, permute,
                                           monkeypatch, realize, rng,
                                           request):
    ctx = request.getfixturevalue(world_fixture)
    n = 1200
    with realize(realization.current()._replace(permute=permute)):
        assert _abc_shuffle(monkeypatch, _table(ctx, _mixed_df(n, rng)),
                            ["k"]) == n


@pytest.mark.parametrize("world_fixture", ["local_ctx", "ctx2", "ctx4"])
def test_compressed_edge_columns(world_fixture, monkeypatch, rng, request):
    """INT64_MIN/MAX, negative ranges, single-value, all-null, width-0
    strings, low-cardinality categories — across worlds 1/2/4."""
    ctx = request.getfixturevalue(world_fixture)
    n = 700
    assert _abc_shuffle(monkeypatch, _table(ctx, _edge_df(n, rng)),
                        ["k"]) == n


def test_compressed_skew_and_empty(ctx4, monkeypatch, rng):
    df = _mixed_df(900, rng)
    df["k"] = np.int64(7)  # one hot key
    assert _abc_shuffle(monkeypatch, _table(ctx4, df), ["k"]) == 900
    assert _abc_shuffle(monkeypatch, _table(ctx4, _mixed_df(0, rng)),
                        ["k"]) == 0


def test_compressed_launch_count(ctx4, monkeypatch, rng):
    """The ISSUE-10 budget pin, asserted directly on the jaxpr: the
    compressed exchange is 1 packed all_to_all + 1 count all_gather +
    at most 1 dictionary all_gather, independent of column count."""
    from cylon_tpu.parallel import plane as plane_mod

    world = 4
    shard_cap = 64
    n = world * shard_cap
    df = _mixed_df(n, rng)
    cols = tuple(colmod.from_numpy(df[c].to_numpy(), capacity=n)
                 for c in df.columns)
    targets = jnp.asarray(rng.integers(0, world, n).astype(np.int32))
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", "1")
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_COMPRESS", "1")
    spec = plane_mod.estimate_spec(cols, world=world, shard_cap=shard_cap)
    assert spec is not None
    assert any(e[0] == "dict" for e in spec)  # the string column encodes

    def fn(cc, tgt):
        out_cols, total = shuffle_mod.shuffle_shard(
            cc, None, tgt, world, shard_cap, n, spec=spec)
        return out_cols, jnp.reshape(total, (1,))

    from jax.sharding import PartitionSpec as P

    from cylon_tpu.context import PARTITION_AXIS
    from cylon_tpu.utils import shard_map

    ctx = ctx4
    f = jax.jit(shard_map(fn, mesh=ctx.mesh, in_specs=P(PARTITION_AXIS),
                          out_specs=P(PARTITION_AXIS), check_vma=False))
    jaxpr = jax.make_jaxpr(f)(cols, targets)
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_COMPRESS", raising=False)
    assert _count_prims(jaxpr.jaxpr, _EXCHANGE_PRIMS) == 1
    assert _count_prims(jaxpr.jaxpr, _COUNT_PRIMS) <= 2


def test_compressed_bytes_drop_low_cardinality(ctx4, monkeypatch, rng):
    """The acceptance meter: >= 1.5x shuffle.bytes_sent drop on the
    goldened low-cardinality workload (narrow int keys + category
    strings), with bit-identical shards asserted by the arms above."""
    from cylon_tpu.obs import metrics as obs_metrics

    n = 2000
    cats = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE"], object)
    df = pd.DataFrame({
        "k": rng.integers(0, 100, n).astype(np.int64),
        "seg": cats[rng.integers(0, 3, n)],
        "date": rng.integers(0, 2556, n).astype(np.int32),
        "price": rng.random(n).astype(np.float32),
    })
    t = _table(ctx4, df)
    sent = {}
    for label, comp in (("plain", "0"), ("comp", "1")):
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", "1")
        monkeypatch.setenv("CYLON_TPU_SHUFFLE_COMPRESS", comp)
        before = obs_metrics.counter_value("shuffle.bytes_sent")
        t.shuffle(["k"])
        sent[label] = obs_metrics.counter_value("shuffle.bytes_sent") - before
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_COMPRESS", raising=False)
    assert sent["comp"] > 0
    assert sent["plain"] / sent["comp"] >= 1.5, sent
    assert obs_metrics.counter_value("shuffle.bytes_saved") > 0


def test_build_spec_units(rng):
    """Host-side spec math: narrowing, raw fallbacks, dictionary vs
    truncation selection."""
    from cylon_tpu.parallel import plane as plane_mod

    n = 64
    cols = (
        colmod.from_numpy(rng.integers(100, 300, n).astype(np.int64)),
        colmod.from_numpy(np.array([np.iinfo(np.int64).min,
                                    np.iinfo(np.int64).max] * 32,
                                   np.int64)),
        colmod.from_numpy(np.full(n, -9, np.int64)),
        colmod.from_numpy(rng.random(n).astype(np.float32)),
        colmod.from_numpy(np.array(["x", "yy"], object)[
            rng.integers(0, 2, n)]),
    )
    spec = plane_mod.estimate_spec(cols, world=4, shard_cap=n)
    assert spec[0][0] == "narrow" and spec[0][2] <= 12   # range 200
    assert spec[1] == ("raw",)                           # full i64 span
    assert spec[2][0] == "narrow" and spec[2][1] == -9 and spec[2][2] == 0
    assert spec[3] == ("raw",)                           # float: raw bits
    assert spec[4][0] == "dict"                          # 2 distinct values
    # all-raw normalizes to None so baseline programs are reused
    raw_cols = (colmod.from_numpy(np.array(
        [np.iinfo(np.int64).min, np.iinfo(np.int64).max] * 32, np.int64)),)
    assert plane_mod.estimate_spec(raw_cols, world=4, shard_cap=n) is None


def test_plane_roundtrip_with_spec(rng):
    """Narrow + truncated encodings round-trip bit-exactly without any
    collective (the dictionary arm is exercised by the shuffle tests)."""
    from cylon_tpu.parallel import plane as plane_mod

    n = 64
    cols = (
        colmod.from_numpy(rng.integers(-50, 1000, n).astype(np.int64)),
        colmod.from_numpy(rng.integers(0, 7, n).astype(np.int16)),
        colmod.from_numpy(np.array(["ab", "", "c"], object)[
            rng.integers(0, 3, n)]),
    )
    spec = plane_mod.estimate_spec(cols, world=4, shard_cap=n)
    # force the string column onto the truncation arm (dict needs the
    # gather collective)
    spec = tuple(("trunc", e[1], 8) if e[0] == "dict" else e for e in spec)
    assert plane_mod.plane_words(cols, spec) < plane_mod.plane_words(cols)
    out = plane_mod.unpack_plane(plane_mod.pack_plane(cols, spec), cols,
                                 spec=spec)
    _assert_cols_equal(cols, out, "spec-roundtrip")
