"""Column-packed single-collective exchange plane.

The shuffle is the framework's central primitive (reference:
cpp/src/cylon/arrow/arrow_all_to_all.cpp:24-236), yet the per-buffer
exchange launches one collective PER BUFFER PER COLUMN — data, validity,
and lengths each pay their own ``all_to_all`` / ``ragged_all_to_all``, so
a 10-column table fires ~30 collectives per exchange.  On XLA the launch
count and payload layout, not FLOPs, dominate collective cost ("Memory-
efficient array redistribution through portable collective communication",
arxiv 2112.01075; EQuARX, arxiv 2506.17615): few large transfers saturate
ICI/DCN where many small ones serialize on launch overhead.

This module bit-packs every column's data/validity/lengths buffers into
ONE contiguous ``uint32[rows, words]`` plane per shard — the same
packed-word discipline ``ops/keys.py::pack_operands`` proved for sort
operands, except the plane is a round-trip format (bit-exact decode), not
an order-preserving encoding — so the whole table moves in a single
collective and is unpacked on the receiver.  Field layout is a pure
function of static column metadata (dtypes, string widths), so sender and
receiver agree by construction inside one SPMD program:

- validity        -> 1 bit
- bool data       -> 1 bit
- 8/16-bit data   -> 8/16 bits (bitcast to unsigned)
- 32-bit data     -> one u32 word (bitcast)
- 64-bit data     -> two u32 words (bitcast)
- string data     -> ceil(width/4) u32 words (4 bytes big-endian each)
- string lengths  -> one u32 word

Words are assigned first-fit-decreasing, so every 32-bit field owns one
word and the sub-word fields (validity bits, bool/8/16-bit data) pack
densely into the remainder — a narrow 10-column i32 table is 11 words
(44 B/row) in ONE collective vs 50 B/row across 20 collectives unpacked.

Gated by ``CYLON_TPU_SHUFFLE_PACK`` (auto = on for TPU backends, the
``ops/compact.py::permute_mode`` precedent); A/B arms live in
tools/microbench.py.

Compression (PR 10, ``CYLON_TPU_SHUFFLE_COMPRESS``): an optional stage
between pack and exchange that shrinks each field to the bits its
*realized* values need — exact by construction, unlike EQuARX's lossy
quantized collectives (arxiv 2506.17615), and living in the data layout
rather than a custom collective (arxiv 2112.01075):

- integer columns narrow to ``("narrow", offset, bits)``: the plane field
  carries ``value - offset`` in ``bits`` bits, where ``offset``/``bits``
  come from the observed min/max over the LIVE rows (null rows' raw
  payload bits included, so they round-trip exactly); a single-value
  column costs 0 bits;
- string columns truncate to ``("trunc", nbytes, len_bits)``: data words
  beyond the observed nonzero-byte extent are all-zero by observation and
  drop out, and the lengths field narrows to the observed maximum;
- low-cardinality string columns dictionary-encode to ``("dict", nbytes,
  lcap, gcap, code_bits)``: rows exchange a ``code_bits``-wide index into
  a global dictionary every shard derives identically from ONE small
  all-gather of per-shard local dictionaries (code 0 is reserved for the
  all-zero row so unwritten ragged tails decode to zeros).

The spec is data-dependent static layout, so it participates in every
jit-plan cache key that reaches a spec-shaped body (cylint rule CY109)
and in the durable/plan fingerprints via the input content they already
hash.  ``CYLON_TPU_SHUFFLE_COMPRESS=0`` is the exact PR-2 baseline:
identical programs, bit-identical shards.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import config, precision
from ..column import Column
from ..obs import stage

_UINT_OF = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}

#: per-column spec entry for the uncompressed (PR-2) field layout
RAW: Tuple = ("raw",)

#: sentinel key word for dictionary padding entries: sorts after every
#: real value (no real row can carry length 2^64-1, so the sentinel can
#: never collide with a live key tuple)
_SENT64 = jnp.uint64(0xFFFFFFFFFFFFFFFF)

#: largest global dictionary worth gathering: past this the per-exchange
#: all-gather stops being "small" relative to the payload it shrinks
_DICT_GCAP_MAX = 4096


def pack_enabled() -> bool:
    """Whether shuffle exchanges move one packed u32 plane instead of one
    collective per buffer per column.  CYLON_TPU_SHUFFLE_PACK=1/0
    overrides; "auto" (default) packs on TPU-family backends, where
    collective launch count dominates, and stays per-buffer elsewhere.
    Read at trace time — callers key their jit caches on it."""
    mode = config.knob("CYLON_TPU_SHUFFLE_PACK")
    if mode in ("1", "on", "packed"):
        return True
    if mode in ("0", "off", "perbuf"):
        return False
    return precision.on_tpu()


def compress_enabled() -> bool:
    """Whether shuffle exchanges may bit-width-reduce / dictionary-encode
    the packed plane (CYLON_TPU_SHUFFLE_COMPRESS; auto = on for
    TPU-family backends, where payload bits over ICI are the cost).
    Compression rides the packed plane, so callers additionally require
    ``pack_enabled()``.  Read at trace time — the knob is in the
    trace_cache_token, and the data-derived spec itself must ride every
    plan cache key (cylint CY109)."""
    mode = config.knob("CYLON_TPU_SHUFFLE_COMPRESS")
    if mode in ("1", "on"):
        return True
    if mode in ("0", "off"):
        return False
    return precision.on_tpu()


def _string_word_count(col: Column) -> int:
    return (col.string_width + 3) // 4


def _spec_of(cols: Sequence[Column], spec) -> Tuple[Tuple, ...]:
    return tuple(spec) if spec is not None else (RAW,) * len(cols)


def _field_widths(cols: Sequence[Column], spec=None) -> List[int]:
    """Bit width of every plane field, in canonical column order.  Must
    stay the exact mirror of _field_values/_rebuild_columns — the three
    walk one shared field sequence.  ``spec`` (see build_spec) swaps a
    column's raw fields for its compressed encoding's fields."""
    ws: List[int] = []
    for c, enc in zip(cols, _spec_of(cols, spec)):
        ws.append(1)                                  # validity
        if c.is_string:
            if enc[0] == "dict":
                ws.append(enc[4])                     # code field
            elif enc[0] == "trunc":
                ws.extend([32] * ((enc[1] + 3) // 4))  # truncated data
                ws.append(enc[2])                     # narrowed lengths
            else:
                ws.extend([32] * _string_word_count(c))   # data words
                ws.append(32)                             # lengths
        elif c.data.dtype == jnp.bool_:
            ws.append(1)
        elif enc[0] == "narrow":
            ws.append(enc[2])                         # offset-reduced data
        elif c.data.dtype.itemsize == 8:
            ws.extend([32, 32])
        else:
            ws.append(c.data.dtype.itemsize * 8)
    return ws


def _layout(widths: Sequence[int]) -> Tuple[List[Tuple[int, int, int]], int]:
    """First-fit-decreasing assignment of fields to u32 words.  Returns
    (slots, num_words): slots[i] = (word, shift, bits) for field i, MSB-
    aligned within each word.  Zero-bit fields (single-value narrowed
    columns) own no plane bits: their slot is (-1, 0, 0) and decode
    reconstructs them from the spec alone.  Pure static math — both ends
    of the exchange derive the identical layout from column metadata."""
    order = sorted(range(len(widths)), key=lambda i: (-widths[i], i))
    slots: List[Optional[Tuple[int, int, int]]] = [None] * len(widths)
    word, used = -1, 32
    for i in order:
        w = widths[i]
        if w == 0:
            slots[i] = (-1, 0, 0)
            continue
        if used + w > 32:
            word += 1
            used = 0
        slots[i] = (word, 32 - used - w, w)
        used += w
    return slots, word + 1  # type: ignore[return-value]


def plane_words(cols: Sequence[Column], spec=None) -> int:
    """Static u32 word count of the packed plane for this schema (under
    ``spec``'s compressed encodings when given)."""
    return _layout(_field_widths(cols, spec))[1]


def _pack_string_data(data: jax.Array) -> List[jax.Array]:
    """uint8[n, width] byte matrix -> ceil(width/4) u32[n] big-endian
    words (the 4-byte analog of keys.pack_string_words' 8-byte packing)."""
    n, width = data.shape
    pad = (-width) % 4
    if pad:
        data = jnp.concatenate([data, jnp.zeros((n, pad), jnp.uint8)], axis=1)
    nwords = data.shape[1] // 4
    if nwords == 0:
        return []
    w = data.reshape(n, nwords, 4).astype(jnp.uint32)
    shifts = jnp.array([24, 16, 8, 0], jnp.uint32)
    packed = jnp.sum(w << shifts, axis=2, dtype=jnp.uint32)
    return [packed[:, i] for i in range(nwords)]


def _unpack_string_data(words: Sequence[jax.Array], width: int) -> jax.Array:
    """Inverse of _pack_string_data: u32 words -> uint8[n, width].
    ``words`` must be non-empty (zero-width matrices never pack words;
    unpack_plane rebuilds their empty shape directly)."""
    n = words[0].shape[0]
    stacked = jnp.stack(words, axis=1)                    # [n, nwords]
    shifts = jnp.array([24, 16, 8, 0], jnp.uint32)
    bytes_ = ((stacked[:, :, None] >> shifts) & jnp.uint32(0xFF)).astype(
        jnp.uint8).reshape(n, -1)
    return bytes_[:, :width]


def _unpack_string_words64(words: Sequence[jax.Array],
                           width: int) -> jax.Array:
    """u64 big-endian words (keys.pack_string_words layout) ->
    uint8[n, width] — the decode half of the dictionary value store."""
    n = words[0].shape[0]
    stacked = jnp.stack(words, axis=1)                    # [n, nwords]
    shifts = jnp.array([56, 48, 40, 32, 24, 16, 8, 0], jnp.uint64)
    bytes_ = ((stacked[:, :, None] >> shifts) & jnp.uint64(0xFF)).astype(
        jnp.uint8).reshape(n, -1)
    return bytes_[:, :width]


def _narrow_encode(data: jax.Array, offset: int, bits: int) -> jax.Array:
    """value -> u32 field: (value - offset), exact because the observed
    range guarantees 0 <= value - offset < 2^bits for every live row.
    Rows outside the observed range (padding rows the exchange never
    sends) may wrap — their field bits are never decoded."""
    if bits == 0:
        return jnp.zeros(data.shape, jnp.uint32)
    if jnp.issubdtype(data.dtype, jnp.unsignedinteger) \
            and data.dtype.itemsize == 8:
        return (data - jnp.uint64(offset)).astype(jnp.uint32)
    return (data.astype(jnp.int64) - jnp.int64(offset)).astype(jnp.uint32)


def _narrow_decode(field: jax.Array, offset: int, dtype) -> jax.Array:
    """u32 field -> value: offset + field, computed 64-bit wide then cast
    back to the column dtype (exact: the value came from that dtype)."""
    if jnp.issubdtype(dtype, jnp.unsignedinteger) and dtype.itemsize == 8:
        return (jnp.uint64(offset) + field.astype(jnp.uint64)).astype(dtype)
    return (jnp.int64(offset) + field.astype(jnp.int64)).astype(dtype)


def _float64_is_a_float32_pair(dtype) -> bool:
    """A TPU holds a float64 as a pair of float32 -- the value rounded to
    float32 and the rest -- and its compiler has no bitcast to or from a
    64-bit float (nor one that changes a 64-bit integer's width).  The
    pair is read by arithmetic, which the chip does exactly except that
    it flushes a subnormal float32: a value under about 2^-100 in
    magnitude, whose rest is one, comes back as its float32 head
    (PERF.md, PR 27: 196,624 of 196,628 probed values bit for bit)."""
    return jnp.issubdtype(dtype, jnp.floating) and precision.on_tpu()


def _split64(data: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A 64-bit buffer as two u32 words, low word first: its bits, or on a
    TPU a float64's two float32 halves (all the chip holds of it)."""
    if _float64_is_a_float32_pair(data.dtype):
        head = data.astype(jnp.float32)
        rest = jnp.where(jnp.isfinite(head), data - head.astype(data.dtype),
                         0).astype(jnp.float32)
        return (jax.lax.bitcast_convert_type(head, jnp.uint32),
                jax.lax.bitcast_convert_type(rest, jnp.uint32))
    bits = jax.lax.bitcast_convert_type(data, jnp.uint64)
    return ((bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
            (bits >> jnp.uint64(32)).astype(jnp.uint32))


def _join64(first: jax.Array, second: jax.Array, dtype) -> jax.Array:
    """``_split64`` undone.  A pair whose rest is zero is its head as it
    is, so that -0.0, the infinities and the zero rows of an unwritten
    tail come back as they went."""
    if _float64_is_a_float32_pair(dtype):
        head = jax.lax.bitcast_convert_type(first, jnp.float32).astype(dtype)
        rest = jax.lax.bitcast_convert_type(second, jnp.float32)
        return jnp.where(rest == 0, head, head + rest.astype(dtype))
    bits = first.astype(jnp.uint64) | (second.astype(jnp.uint64)
                                       << jnp.uint64(32))
    return jax.lax.bitcast_convert_type(bits, dtype)


def _field_values(cols: Sequence[Column], spec=None,
                  codes: Optional[Dict[int, jax.Array]] = None
                  ) -> List[jax.Array]:
    """u32[n] value array per field (same order as _field_widths); every
    value already fits its declared bit width.  ``codes`` carries the
    per-row dictionary codes for spec "dict" columns (PlaneCodec computes
    them — they need the all-gathered global dictionary)."""
    vals: List[jax.Array] = []
    for i, (c, enc) in enumerate(zip(cols, _spec_of(cols, spec))):
        vals.append(c.validity.astype(jnp.uint32))
        if c.is_string:
            if enc[0] == "dict":
                vals.append((codes or {})[i])
            elif enc[0] == "trunc":
                vals.extend(_pack_string_data(c.data[:, :enc[1]]))
                vals.append(c.lengths.astype(jnp.uint32))
            else:
                vals.extend(_pack_string_data(c.data))
                vals.append(jax.lax.bitcast_convert_type(
                    c.lengths.astype(jnp.int32), jnp.uint32))
        elif c.data.dtype == jnp.bool_:
            vals.append(c.data.astype(jnp.uint32))
        elif enc[0] == "narrow":
            vals.append(_narrow_encode(c.data, enc[1], enc[2]))
        elif c.data.dtype.itemsize == 8:
            vals.extend(_split64(c.data))
        else:
            bits = jax.lax.bitcast_convert_type(
                c.data, _UINT_OF[c.data.dtype.itemsize])
            vals.append(bits.astype(jnp.uint32))
    return vals


@stage("plane.pack")
def pack_plane_words(cols: Sequence[Column], spec=None,
                     codes: Optional[Dict[int, jax.Array]] = None
                     ) -> List[jax.Array]:
    """The columns of ``pack_plane``'s plane, each a uint32[rows] word."""
    slots, nwords = _layout(_field_widths(cols, spec))
    words: List[Optional[jax.Array]] = [None] * nwords
    for (word, shift, bits), v in zip(slots, _field_values(cols, spec,
                                                           codes)):
        if bits == 0:
            continue
        sh = v if shift == 0 else (v << jnp.uint32(shift))
        words[word] = sh if words[word] is None else (words[word] | sh)
    return words


def pack_plane(cols: Sequence[Column], spec=None,
               codes: Optional[Dict[int, jax.Array]] = None) -> jax.Array:
    """Bit-pack the columns' buffers into one uint32[rows, words] plane.
    Bit-exact round trip with unpack_plane (floats travel as raw bits, so
    NaN payloads and -0.0 survive).  With ``spec``, compressed fields are
    laid out instead of raw ones (dict columns need ``codes``)."""
    words = pack_plane_words(cols, spec, codes)
    with stage("plane.pack"):
        if not words:
            return jnp.zeros((cols[0].data.shape[0], 0), jnp.uint32)
        return jnp.stack(words, axis=1)


@stage("plane.unpack")
def unpack_plane(plane: jax.Array, like: Sequence[Column],
                 valid_mask: Optional[jax.Array] = None, spec=None,
                 dicts: Optional[Dict[int, Tuple[jax.Array, ...]]] = None,
                 tail_mask: Optional[jax.Array] = None) -> Tuple[Column, ...]:
    """Decode a packed plane back into Columns with ``like``'s schema
    (dtypes, string widths).  ``valid_mask`` ANDs into every column's
    validity and zeroes masked rows' data/lengths — the exact masking
    Column.take applies, so packed and per-buffer exchanges produce
    bit-identical shards.  ``tail_mask`` (compressed ragged path) forces
    rows beyond it to all-zero buffers WITHOUT touching in-range null
    rows' raw payloads — the unwritten tail of a ragged output buffer
    would otherwise decode to ``offset``/dictionary-entry-0 values
    instead of the zeros the uncompressed realizations produce."""
    widths = _field_widths(like, spec)
    slots, nwords = _layout(widths)
    assert plane.shape[1] == nwords, (plane.shape, nwords)
    it = iter(slots)
    n = plane.shape[0]

    def field() -> jax.Array:
        word, shift, bits = next(it)
        if bits == 0:
            return jnp.zeros((n,), jnp.uint32)
        v = plane[:, word]
        if shift:
            v = v >> jnp.uint32(shift)
        if bits < 32:
            v = v & jnp.uint32((1 << bits) - 1)
        return v

    def _widen(mat: jax.Array, width: int) -> jax.Array:
        if mat.shape[1] == width:
            return mat
        pad = jnp.zeros((n, width - mat.shape[1]), jnp.uint8)
        return jnp.concatenate([mat, pad], axis=1)

    out: List[Column] = []
    for i, (c, enc) in enumerate(zip(like, _spec_of(like, spec))):
        validity = field().astype(jnp.bool_)
        lengths = None
        if c.is_string:
            if enc[0] == "dict":
                idx = field().astype(jnp.int32)
                gws = (dicts or {})[i]
                vals = [jnp.take(w, idx, mode="clip") for w in gws]
                lengths = vals[-1].astype(jnp.int32)
                nbytes = enc[1]
                mat = (_unpack_string_words64(vals[:-1], nbytes) if nbytes
                       else jnp.zeros((n, 0), jnp.uint8))
                data = _widen(mat, c.string_width)
            elif enc[0] == "trunc":
                nbytes = enc[1]
                words = [field() for _ in range((nbytes + 3) // 4)]
                mat = (_unpack_string_data(words, nbytes) if words
                       else jnp.zeros((n, 0), jnp.uint8))
                data = _widen(mat, c.string_width)
                lengths = field().astype(jnp.int32)
            else:
                words = [field() for _ in range(_string_word_count(c))]
                data = (_unpack_string_data(words, c.string_width) if words
                        else jnp.zeros((n, c.string_width), jnp.uint8))
                lengths = jax.lax.bitcast_convert_type(field(), jnp.int32)
        elif c.data.dtype == jnp.bool_:
            data = field().astype(jnp.bool_)
        elif enc[0] == "narrow":
            data = _narrow_decode(field(), enc[1], c.data.dtype)
        elif c.data.dtype.itemsize == 8:
            data = _join64(field(), field(), c.data.dtype)
        else:
            w = c.data.dtype.itemsize
            data = jax.lax.bitcast_convert_type(
                field().astype(_UINT_OF[w]), c.data.dtype)
        if tail_mask is not None:
            validity = validity & tail_mask
            zero = jnp.zeros((), data.dtype)
            data = jnp.where(tail_mask[:, None] if data.ndim == 2
                             else tail_mask, data, zero)
            if lengths is not None:
                lengths = jnp.where(tail_mask, lengths, 0)
        if valid_mask is not None:
            validity = validity & valid_mask
            zero = jnp.zeros((), data.dtype)
            data = jnp.where(validity[:, None] if data.ndim == 2 else validity,
                             data, zero)
            if lengths is not None:
                lengths = jnp.where(validity, lengths, 0)
        out.append(Column(data, validity, lengths, c.dtype))
    return tuple(out)


# ---------------------------------------------------------------------------
# compression spec: observed stats -> static field encodings
# ---------------------------------------------------------------------------


def stats_layout(cols: Sequence[Column]) -> Tuple[Optional[str], ...]:
    """Which observation each column needs: "int" (min/max), "str"
    (extent/maxlen/nunique), None (float/bool — raw always).  The shared
    walk order between partition.column_stats (device) and build_spec
    (host): the two must consume the same flat stats sequence."""
    lay: List[Optional[str]] = []
    for c in cols:
        if c.is_string:
            lay.append("str")
        elif c.data.dtype != jnp.bool_ and jnp.issubdtype(c.data.dtype,
                                                          jnp.integer):
            lay.append("int")
        else:
            lay.append(None)
    return tuple(lay)


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _round_bits(bits: int) -> int:
    """Field widths round up to multiples of 4 so small data drift keeps
    hitting the same traced program (the jit-cache-churn bound)."""
    return ((bits + 3) // 4) * 4


def build_spec(cols: Sequence[Column], stats: Sequence, world: int,
               shard_cap: int):
    """Observed per-column stats -> the static compression spec, or None
    when nothing compresses (the all-raw spec normalizes to None so the
    baseline jit programs are reused verbatim).

    ``stats`` is the flat host-side sequence matching stats_layout: two
    values (min, max) per "int" column, three (byte extent, max length,
    max per-shard distinct count) per "str" column.  All values are
    REPLICATED observations (device collectives or a single-controller
    host pass), so every process derives the identical spec — the SPMD
    requirement for a layout that shapes the traced program."""
    import numpy as np

    it = iter(stats)
    spec: List[Tuple] = []
    any_comp = False
    for c, kind in zip(cols, stats_layout(cols)):
        if kind == "int":
            mn = int(np.asarray(next(it)).reshape(-1)[0])
            mx = int(np.asarray(next(it)).reshape(-1)[0])
            raw_bits = c.data.dtype.itemsize * 8
            if mx < mn:                      # no live rows anywhere
                spec.append(("narrow", 0, 0))
                any_comp = True
                continue
            span = mx - mn                   # exact Python-int arithmetic
            bits = _round_bits(span.bit_length())
            if bits <= 32 and bits < raw_bits:
                spec.append(("narrow", mn, bits))
                any_comp = True
            else:
                spec.append(RAW)
        elif kind == "str":
            extent = int(np.asarray(next(it)).reshape(-1)[0])
            maxlen = int(np.asarray(next(it)).reshape(-1)[0])
            nun = int(np.asarray(next(it)).reshape(-1)[0])
            len_bits = _round_bits(maxlen.bit_length())
            raw_cost = 32 * _string_word_count(c) + 32
            trunc_cost = 32 * ((extent + 3) // 4) + len_bits
            lcap = min(_pow2(max(1, nun)), max(1, int(shard_cap)))
            gcap = 1 + world * lcap
            code_bits = _round_bits(max(1, (gcap - 1).bit_length()))
            if nun > 0 and gcap <= _DICT_GCAP_MAX \
                    and code_bits < min(trunc_cost, raw_cost):
                spec.append(("dict", extent, lcap, gcap, code_bits))
                any_comp = True
            elif trunc_cost < raw_cost:
                spec.append(("trunc", extent, len_bits))
                any_comp = True
            else:
                spec.append(RAW)
        else:
            spec.append(RAW)
    return tuple(spec) if any_comp else None


def estimate_spec(cols: Sequence[Column], world: int, shard_cap: int,
                  count=None):
    """Host-side spec from locally addressable buffers (np.asarray pulls
    them) — for ADVISORY consumers only: plan.explain annotations, the
    microbench A/B, and the budget tracer's direct ragged trace.  The
    real exchange derives its spec from the replicated device stats pass
    (partition.column_stats) so multi-controller processes can never
    disagree on the layout."""
    import numpy as np

    n = cols[0].data.shape[0] if cols else 0
    live_n = n if count is None else int(count)
    stats: List[int] = []
    for c, kind in zip(cols, stats_layout(cols)):
        if kind == "int":
            d = np.asarray(c.data)[:live_n]
            if d.size == 0:
                stats.extend([0, -1])
            else:
                stats.extend([int(d.min()), int(d.max())])
        elif kind == "str":
            mat = np.asarray(c.data)[:live_n]
            lens = np.asarray(c.lengths)[:live_n]
            if mat.shape[0] == 0:
                stats.extend([0, 0, 1])
                continue
            nz = np.nonzero(mat.any(axis=0))[0]
            extent = int(nz[-1]) + 1 if nz.size else 0
            maxlen = int(lens.max()) if lens.size else 0
            pad = (-mat.shape[1]) % 8
            if pad:
                mat = np.concatenate(
                    [mat, np.zeros((mat.shape[0], pad), np.uint8)], axis=1)
            rows = np.concatenate(
                [mat, lens.astype(np.int64).view(np.uint8).reshape(
                    len(lens), 8)], axis=1)
            nun = len(np.unique(rows.view(
                [("", np.uint8, rows.shape[1])])))
            stats.extend([extent, maxlen, nun])
    return build_spec(cols, stats, world, shard_cap)


# ---------------------------------------------------------------------------
# dictionary key machinery — SHARED by the observation pass
# (partition.column_stats sizes lcap from a distinct-count upper bound)
# and the codec (which builds the actual local dictionary): both must
# walk the identical key space or the dictionary silently overflows lcap
# ---------------------------------------------------------------------------


def string_key_words(c: Column, nbytes: Optional[int] = None
                     ) -> List[jax.Array]:
    """THE dictionary key tuple for one string column: big-endian u64
    data words (optionally truncated to ``nbytes`` — truncation can only
    merge keys, so a full-width distinct count stays an upper bound)
    plus the length word."""
    from ..ops import keys as keys_mod

    data = c.data if nbytes is None else c.data[:, :nbytes]
    kws = keys_mod.pack_string_words(data) if data.shape[1] else []
    return kws + [c.lengths.astype(jnp.uint64)]


def sorted_distinct_flags(kws: Sequence[jax.Array]
                          ) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """lex-sort the key tuple and flag the first row of every distinct
    group: (sorted words, bool flag).  ``sum(flag)`` is the distinct
    count; compacting the flagged rows yields the sorted dictionary."""
    swv = jax.lax.sort(tuple(kws), num_keys=len(kws), is_stable=False)
    if not isinstance(swv, (tuple, list)):
        swv = (swv,)
    neq = functools.reduce(
        lambda a, b: a | b, [w[1:] != w[:-1] for w in swv])
    flag = jnp.concatenate([jnp.ones((1,), jnp.bool_), neq])
    return tuple(swv), flag


# ---------------------------------------------------------------------------
# codec: the spec applied to one shard's columns (dictionary build is a
# collective, so codecs are constructed INSIDE the shard body)
# ---------------------------------------------------------------------------


class PlaneCodec:
    """pack/unpack under one compression spec.  ``spec=None`` is the
    exact PR-2 baseline (no extra ops traced).  Dictionary columns cost
    ONE all_gather total at construction — every shard derives the
    identical sorted global dictionary from the gathered per-shard local
    dictionaries, so sender codes decode on any receiver."""

    def __init__(self, cols: Sequence[Column], spec=None):
        self.spec = spec
        self.codes: Dict[int, jax.Array] = {}
        self.dicts: Dict[int, Tuple[jax.Array, ...]] = {}
        if spec is None:
            return
        dcols = [(i, e) for i, e in enumerate(spec) if e[0] == "dict"]
        if not dcols:
            return
        from ..obs import spans as obs_spans
        from ..ops import compact as compact_mod
        from . import collectives

        def _distinct_sorted(kws: Sequence[jax.Array], keep: int):
            """(sorted distinct prefix padded with sentinels, count)."""
            swv, flag = sorted_distinct_flags(kws)
            perm, m = compact_mod.compact_indices(flag)
            sel = perm[:keep]
            ok = jnp.arange(keep, dtype=jnp.int32) < m
            return [jnp.where(ok, jnp.take(w, sel, mode="clip"), _SENT64)
                    for w in swv], m

        with obs_spans.span("shuffle.dict_gather", columns=len(dcols)):
            locals_: List[Tuple[int, Tuple, List[jax.Array],
                                List[jax.Array]]] = []
            for i, e in dcols:
                _, nbytes, lcap, gcap, code_bits = e
                kws = string_key_words(cols[i], nbytes)
                loc, _m = _distinct_sorted(kws, lcap)
                locals_.append((i, e, kws, loc))
            # ONE gather for every dictionary column: pad to a common
            # word count and concatenate rows
            maxk = max(len(loc) for _, _, _, loc in locals_)
            blocks = []
            for _i, _e, _kws, loc in locals_:
                padded = loc + [jnp.full_like(loc[0], _SENT64)
                                ] * (maxk - len(loc))
                blocks.append(jnp.stack(padded, axis=1))   # [lcap, maxk]
            buf = jnp.concatenate(blocks, axis=0)
            # all_gather stacks a new leading mesh axis: [world, rows, k]
            g3 = collectives.allgather(buf, axis=0)
            world = g3.shape[0]
        off = 0
        for i, e, kws, loc in locals_:
            _, nbytes, lcap, gcap, code_bits = e
            k = len(loc)
            block = g3[:, off:off + lcap, :k].reshape(world * lcap, k)
            off += lcap
            # code 0 is the all-zero row by construction: prepend it so
            # unwritten ragged tails (zero codes) decode to zero buffers
            gl = [jnp.concatenate([jnp.zeros((1,), jnp.uint64),
                                   block[:, j]]) for j in range(k)]
            gd, _g = _distinct_sorted(gl, gcap)
            self.dicts[i] = tuple(gd)
            # per-row codes: merged sort of (dict entries, rows) with a
            # dict-first marker — a row's code is the index of its value
            # in the sorted distinct dictionary (cumsum of dict entries
            # seen), scattered back to row order
            cap = cols[i].data.shape[0]
            keys_m = [jnp.concatenate([gd[j], kws[j]]) for j in range(k)]
            marker = jnp.concatenate([jnp.zeros((gcap,), jnp.bool_),
                                      jnp.ones((cap,), jnp.bool_)])
            payload = jnp.concatenate([jnp.zeros((gcap,), jnp.int32),
                                       jnp.arange(cap, dtype=jnp.int32)])
            srt = jax.lax.sort(tuple(keys_m) + (marker, payload),
                               num_keys=k + 1, is_stable=True)
            marker_s, payload_s = srt[-2], srt[-1]
            dictpos = jnp.cumsum((~marker_s).astype(jnp.int32)) - 1
            target = jnp.where(marker_s, payload_s, cap)
            self.codes[i] = jnp.zeros((cap + 1,), jnp.uint32).at[
                target].set(dictpos.astype(jnp.uint32))[:cap]

    def pack(self, cols: Sequence[Column]) -> jax.Array:
        return pack_plane(cols, self.spec, self.codes)

    def pack_words(self, cols: Sequence[Column]) -> List[jax.Array]:
        return pack_plane_words(cols, self.spec, self.codes)

    def unpack(self, plane: jax.Array, like: Sequence[Column],
               valid_mask: Optional[jax.Array] = None,
               tail_mask: Optional[jax.Array] = None) -> Tuple[Column, ...]:
        return unpack_plane(plane, like, valid_mask=valid_mask,
                            spec=self.spec, dicts=self.dicts,
                            tail_mask=tail_mask)
