"""Path-targeted tests for the lexsort encodings.

``keys.lexsort_indices`` has three executable shapes — single-u32-word
(key fields + index <= 32 bits), double-u32-word (<= 64 bits), and the
multi-word packed fallback — and the shuffle's counting-scan split has a
``lax.sort`` fallback past 32 targets.  Each path must agree with a
numpy stable reference, including null ordering, descending flips, NaN
canonicalization, and -0.0 == +0.0.
"""
import numpy as np
import pytest


def _device_perm(cols_np, count, cap, ascending=None):
    import jax.numpy as jnp

    from cylon_tpu import column as colmod
    from cylon_tpu.ops import keys

    cols = []
    for data, valid in cols_np:
        # validity passed explicitly so NaN cells survive ingestion as
        # values (from_numpy's default treats NaN as null and zeroes it)
        cols.append(colmod.from_numpy(data, validity=valid))
    ops = keys.build_operands(cols, jnp.asarray(count, jnp.int32), cap,
                              ascending=ascending)
    perm, sorted_ops, _ = keys.lexsort_indices(ops, cap)
    return np.asarray(perm), [np.asarray(o) for o in sorted_ops]


@pytest.mark.parametrize("dtype,cap", [
    (np.int16, 64),      # single-word path: 1+1+16+6 <= 32
    (np.int32, 64),      # double-word path: 1+1+32+6 <= 64
    (np.float32, 64),    # double-word path incl. float canonicalization
    (np.float64, 64),    # fallback: 64-bit field
])
def test_lexsort_paths_match_numpy(dtype, cap, rng):
    import jax.numpy as jnp

    from cylon_tpu.ops import keys

    count = 50
    if np.issubdtype(dtype, np.floating):
        data = rng.standard_normal(cap).astype(dtype)
        data[3] = np.nan
        data[7] = -0.0
        data[9] = 0.0
    else:
        data = rng.integers(-40, 40, cap).astype(dtype)
    valid = rng.random(cap) > 0.2
    perm, sorted_ops = _device_perm([(data, valid)], count, cap)

    # permutation property
    assert sorted(perm.tolist()) == list(range(cap))
    # padding last
    assert set(perm[count:].tolist()) == set(range(count, cap))
    # live region ordered: nulls first, then ascending canonical values
    lived = [(bool(valid[i]),
              data[i]) for i in perm[:count]]
    nulls = [x for x in lived if not x[0]]
    vals = [x[1] for x in lived if x[0]]
    assert lived[:len(nulls)] == nulls, "nulls must sort first"

    def canon(v):
        # canonical sort key: NaN above +inf (the total-order encoding),
        # -0.0 folded into +0.0
        if np.issubdtype(dtype, np.floating):
            if np.isnan(v):
                return np.inf  # ties with +inf are fine for the <= check
            return 0.0 if v == 0 else float(v)
        return int(v)

    cv = [canon(v) for v in vals]
    assert cv == sorted(cv)
    if np.issubdtype(dtype, np.floating):
        # NaN must land at the very end of the live values
        assert np.isnan(vals[-1]) or not any(np.isnan(v) for v in vals)
        # equality words: -0.0 groups with +0.0
        eq = np.asarray(keys.rows_equal_adjacent(
            [jnp.asarray(o) for o in sorted_ops]))
        live_pos = {int(p): k for k, p in enumerate(perm[:count])}
        zpos = sorted(live_pos[i] for i in (7, 9) if valid[i])
        if len(zpos) == 2 and zpos[1] == zpos[0] + 1:
            assert eq[zpos[1]], "-0.0 and +0.0 must share a key"


def test_lexsort_descending_all_paths(rng):
    from cylon_tpu.ops import keys  # noqa: F401

    for dtype in (np.int16, np.int32, np.float64):
        cap, count = 32, 32
        data = (rng.standard_normal(cap).astype(dtype)
                if np.issubdtype(dtype, np.floating)
                else rng.integers(-99, 99, cap).astype(dtype))
        valid = np.ones(cap, bool)
        perm, _ = _device_perm([(data, valid)], count, cap,
                               ascending=[False])
        got = data[perm]
        exp = np.sort(data)[::-1]
        np.testing.assert_array_equal(got, exp)


def test_perm_by_target_wide_mesh_fallback(rng):
    """world > 31 takes the lax.sort fallback; both must agree."""
    import jax.numpy as jnp

    from cylon_tpu.parallel import shuffle

    n = 1000
    for world in (8, 40):  # counting scan vs sort fallback
        targets = jnp.asarray(
            np.append(rng.integers(0, world, n - 5), [world] * 5)  # 5 padding
            .astype(np.int32))
        perm = np.asarray(shuffle._perm_by_target(targets, world)[0])
        t = np.asarray(targets)
        # stable grouping: targets nondecreasing, ties in original order
        g = t[perm]
        assert (np.diff(g) >= 0).all()
        for tv in range(world + 1):
            idx = perm[g == tv]
            assert (np.diff(idx) > 0).all(), "must be stable within target"


def test_target_counts_wide_mesh_sort_mode(rng, realize):
    """sort permute mode switches from the dense alphabet compare to the
    sort + count_leq_dense derivation past world=32 (round-4 advice: the
    O(cap*world) broadcast intermediate); every path must agree with the
    scatter-mode segment_sum, including padding (== world) and the
    out-of-range remap."""
    import jax.numpy as jnp

    from cylon_tpu.ops import realization
    from cylon_tpu.parallel import shuffle

    n = 4096
    cases = {}
    for world in (8, 40, 100):
        t = np.append(rng.integers(0, world, n - 7),
                      [world] * 5 + [-3, world + 9]).astype(np.int32)
        cases[world] = (t, np.bincount(t[(t >= 0) & (t < world)],
                                       minlength=world))
    for permute in ("scatter", "sort"):
        with realize(realization.current()._replace(permute=permute)):
            for world, (t, expected) in cases.items():
                got = np.asarray(shuffle.target_counts(jnp.asarray(t), world))
                np.testing.assert_array_equal(got, expected)


def test_compact_index_dtype_selection():
    """Index dtype promotes to int64 only past 2^31 rows (round-4 advice:
    the fallback the guard exists for must not wrap int32)."""
    import jax.numpy as jnp

    from cylon_tpu.ops import compact

    assert compact._idx_dtype(1 << 20) == jnp.int32
    assert compact._idx_dtype((1 << 31) - 1) == jnp.int32
    assert compact._idx_dtype(1 << 31) == jnp.int64
    assert compact._idx_dtype((1 << 31) + 7) == jnp.int64


def test_lexsort_64bit_boundary(rng):
    """3 x i16 keys: pad(1) + 3*(validity+16) = 52 bits; cap 4096 gives
    idx_bits 12 -> exactly 64 (fast path ceiling), cap 8192 gives 65 ->
    multi-word fallback.  Both must produce the same multiset grouping as
    a numpy lexsort."""
    import jax.numpy as jnp

    from cylon_tpu.ops import keys

    for cap in (4096, 8192):
        count = cap - 37
        cols_np = [rng.integers(-5, 5, cap).astype(np.int16) for _ in range(3)]
        perm, sorted_ops = _device_perm(
            [(c, np.ones(cap, bool)) for c in cols_np], count, cap)
        assert sorted(perm.tolist()) == list(range(cap))
        assert set(perm[count:].tolist()) == set(range(count, cap))
        got = [tuple(int(c[i]) for c in cols_np) for i in perm[:count]]
        exp = sorted(tuple(int(c[i]) for c in cols_np) for i in range(count))
        assert got == exp, f"cap={cap}"
        # equality words break exactly at key changes
        eq = np.asarray(keys.rows_equal_adjacent(
            [jnp.asarray(o) for o in sorted_ops]))[:count]
        exp_eq = [False] + [got[i] == got[i - 1] for i in range(1, count)]
        assert eq.tolist() == exp_eq, f"cap={cap}"


def _bits(x):
    """A payload's bytes, row by row, so NaN payloads and -0.0 compare."""
    x = np.asarray(x)
    return x.view(np.uint8).reshape(x.shape[0], -1)


_NAN_BITS = {np.float32: np.uint32(0x7FC0BEEF),
             np.float64: np.uint64(0x7FF80000DEADBEEF)}


def _payload(dtype, cap, rng):
    if dtype is np.bool_:
        return rng.random(cap) > 0.5
    if np.issubdtype(dtype, np.floating):
        x = rng.standard_normal(cap).astype(dtype)
        x[1], x[2] = -0.0, 0.0
        nan = _NAN_BITS[dtype]
        x.view(nan.dtype)[[3, 5]] = nan, nan | nan.dtype.type(1 << 31)
        return x
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, cap, dtype=dtype)


@pytest.mark.parametrize("payload_dtype", [np.bool_, np.int32, np.int64,
                                           np.float32, np.float64])
@pytest.mark.parametrize("key_dtype", [np.int16, np.int32, np.float64],
                         ids=["one_word", "two_words", "general"])
def test_lexsort_payload_rides_bit_for_bit(key_dtype, payload_dtype, rng):
    """Each shape of the sort returns its payload as ``take(x, perm)`` would,
    bit for bit, through ``pack_payload`` and back; and the permutation and
    the sorted words are those of the sort without payload."""
    import jax.numpy as jnp

    from cylon_tpu import column as colmod
    from cylon_tpu.ops import keys

    cap, count = 64, 50
    key = colmod.from_numpy(rng.integers(-9, 9, cap).astype(key_dtype),
                            validity=rng.random(cap) > 0.2)
    ops = keys.build_operands([key], jnp.asarray(count, jnp.int32), cap)
    buffers = [jnp.asarray(_payload(payload_dtype, cap, rng)),
               jnp.asarray(_payload(payload_dtype, cap, rng))]

    perm0, words0, none = keys.lexsort_indices(ops, cap)
    assert none == []
    assert [str(w.dtype) for w in words0] == {       # the shape that ran
        np.int16: ["uint32"], np.int32: ["uint32"] * 2,
        np.float64: ["uint32", "uint64"]}[key_dtype]
    lanes, layout = keys.pack_payload(buffers)
    assert None not in layout
    assert len(lanes) == (1 if payload_dtype is np.bool_ else 2)
    perm, words, lanes = keys.lexsort_indices(ops, cap, lanes)
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(perm0))
    for w, w0 in zip(words, words0, strict=True):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(w0))
    for moved, x in zip(keys.unpack_payload(lanes, layout), buffers,
                        strict=True):
        assert moved.dtype == x.dtype
        np.testing.assert_array_equal(_bits(moved),
                                      _bits(np.asarray(x)[np.asarray(perm)]))


def test_pack_payload_leaves_to_the_take_what_cannot_ride():
    """A byte matrix never rides; lanes past the constant do not; validity
    bits share words of 32.  The counters say how many 32-bit lanes went
    each way."""
    import jax.numpy as jnp

    from cylon_tpu.obs import metrics
    from cylon_tpu.ops import compact, keys

    cap = 16
    flags = [jnp.arange(cap) % (i + 2) == 0 for i in range(33)]
    matrix = jnp.zeros((cap, 12), jnp.uint8)
    wide = [jnp.arange(cap, dtype=jnp.int64)] * compact.MAX_PAYLOAD_LANES

    def counted(buffers):
        before = [metrics.counter_value(f"sort.{k}_lanes")
                  for k in ("payload", "take")]
        lanes, layout = keys.pack_payload(buffers)
        after = [metrics.counter_value(f"sort.{k}_lanes")
                 for k in ("payload", "take")]
        return lanes, layout, [a - b for a, b in zip(after, before)]

    lanes, layout, (rode, took) = counted(flags + [matrix])
    assert [lane.dtype for lane in lanes] == [jnp.uint32, jnp.uint32]
    assert layout[-1] is None and None not in layout[:-1]
    assert (rode, took) == (2, 3)
    for flag, back in zip(flags, keys.unpack_payload(lanes, layout)):
        np.testing.assert_array_equal(np.asarray(back), np.asarray(flag))

    lanes, layout, (rode, took) = counted(flags[:1] + wide)
    budget = compact.MAX_PAYLOAD_LANES - 1           # one word of validity
    assert rode == 1 + budget // 2 * 2 and rode + took == 1 + 2 * len(wide)
    assert [w is None for w in layout[1:]] == [
        i >= budget // 2 for i in range(len(wide))]


@pytest.mark.parametrize("dtypes_", [("uint32",) * 5,
                                     ("uint32", "uint64", "uint64", "uint64",
                                      "uint64")],
                         ids=["five_u32", "flags_and_a_32_byte_string"])
def test_word_by_word_argsort_is_the_stable_lexicographic_order(dtypes_):
    """Keys of more than ``_MAX_KEY_WORDS`` packed words (string keys) are
    sorted a 32-bit word at a time; the order is the one the many-key
    stable sort gives, ties in row order."""
    import jax.numpy as jnp

    from cylon_tpu.ops import keys

    rng = np.random.default_rng(3)
    n = 4096
    # few distinct values a word: ties on every prefix
    packed = [rng.integers(0, 3, n).astype(dt) << (40 if dt == "uint64"
                                                   else 0)
              | rng.integers(0, 2, n).astype(dt) for dt in dtypes_]
    assert len(packed) > keys._MAX_KEY_WORDS
    perm = np.asarray(keys._word_by_word_argsort(
        [jnp.asarray(p) for p in packed], n))
    want = np.lexsort([p for p in reversed(packed)])   # stable
    assert np.array_equal(perm, want)
    # and through lexsort_indices: the sorted words come back too
    got_perm, sorted_words, _ = keys.lexsort_indices(
        [jnp.asarray(p) for p in packed], n)
    assert np.array_equal(np.asarray(got_perm), want)
    as_packed = keys._pack_encoded(
        [keys._ordered_unsigned(jnp.asarray(p)) for p in packed])
    assert len(sorted_words) == len(as_packed)
    for word, p in zip(sorted_words, as_packed):
        assert np.array_equal(np.asarray(word), np.asarray(p)[want])
