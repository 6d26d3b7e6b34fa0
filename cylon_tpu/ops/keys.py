"""Row-key encoding for sort/equality kernels.

The reference compares rows through virtual-dispatch comparator objects
(cpp/src/cylon/arrow/arrow_comparator.hpp:25-189) and sorts via index
quicksorts (arrow/arrow_kernels.hpp:180-314, util/sort.hpp).  On TPU the
idiomatic equivalent is ``jax.lax.sort`` with **multiple key operands**
(lexicographic, one fused XLA sort), so this module turns typed columns into
flat sortable operands:

- numeric column  -> [validity_key, data]  (nulls ordered first/last)
- string column   -> [validity_key, w0, w1, ...] where wi are big-endian
  uint64 words packed from the zero-padded byte matrix; zero padding keeps
  bytewise lexicographic order identical to string order.
- the row-padding flag is always the first operand so rows beyond the dynamic
  row count sort to the back of every permutation.

Row equality (multi-column, the job of TableRowComparator) becomes adjacent
comparison of these operands after a lexsort, which then yields dense group
ids via a prefix sum — the backbone of groupby/unique/set-ops/joins here.

Rows that have to follow a sort's permutation ride a sort as non-key
*payload* operands (``pack_payload`` / ``lexsort_indices`` /
``unpack_payload``) and come back sorted: on a TPU a 32-bit lane through a
sort costs a tenth of the same lane through an index vector.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..column import Column
from ..obs import metrics as obs_metrics, stage
from . import compact


def pack_string_words(data: jax.Array) -> List[jax.Array]:
    """Pack a uint8[n, L] byte matrix into ceil(L/8) uint64[n] big-endian
    words; lexicographic order on the word tuple == bytewise order."""
    n, width = data.shape
    pad = (-width) % 8
    if pad:
        data = jnp.concatenate([data, jnp.zeros((n, pad), jnp.uint8)], axis=1)
    nwords = data.shape[1] // 8
    words = data.reshape(n, nwords, 8).astype(jnp.uint64)
    shifts = jnp.array([56, 48, 40, 32, 24, 16, 8, 0], jnp.uint64)
    packed = jnp.sum(words << shifts, axis=2, dtype=jnp.uint64)
    return [packed[:, i] for i in range(nwords)]


def column_operands(col: Column, *, nulls_first: bool = True,
                    with_validity: bool = True) -> List[jax.Array]:
    """Sortable operands for one column (most-significant first).  Boolean
    operands stay ``bool`` so the bit-packer can store them in 1 bit."""
    ops: List[jax.Array] = []
    if with_validity:
        if nulls_first:
            ops.append(col.validity)       # invalid(0) < valid(1)
        else:
            ops.append(~col.validity)      # valid(0) < invalid(1)
    if col.is_string:
        ops.extend(pack_string_words(col.data))
    else:
        ops.append(col.data)
    return ops


def padding_operand(capacity: int, row_count) -> jax.Array:
    """First sort operand: False for live rows, True for padding, so padding
    always lands at the back."""
    return jnp.arange(capacity, dtype=jnp.int32) >= row_count


def build_operands(cols: Sequence[Column], row_count, capacity: int,
                   *, ascending: Sequence[bool] | None = None,
                   nulls_first: bool = True) -> List[jax.Array]:
    """All sort operands for a multi-column key, padding flag first.

    Descending order per column is realized by bit-flipping that column's
    operands (works for the unsigned encodings; for signed/float data we
    negate via the order-preserving unsigned reinterpretation).
    """
    ops: List[jax.Array] = [padding_operand(capacity, row_count)]
    for i, col in enumerate(cols):
        col_ops = column_operands(col, nulls_first=nulls_first)
        if ascending is not None and not ascending[i]:
            # flip the DATA order only: null placement is governed by
            # nulls_first alone, independent of per-column direction
            # (pandas na_position semantics — inverting the validity
            # operand would silently send nulls to the other end on
            # descending columns)
            col_ops = [col_ops[0]] + [_invert_operand(o)
                                      for o in col_ops[1:]]
        ops.extend(col_ops)
    return ops


def _invert_operand(x: jax.Array) -> jax.Array:
    """Order-reversing transform for one operand."""
    if x.dtype == jnp.bool_:
        return ~x
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        return ~x
    if jnp.issubdtype(x.dtype, jnp.signedinteger):
        return -1 - x  # maps min->max order-reversed without overflow on wrap
    if jnp.issubdtype(x.dtype, jnp.floating):
        return -x
    return ~x.astype(jnp.uint8)


_UINT_OF = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _ordered_unsigned(x: jax.Array) -> Tuple[jax.Array, int]:
    """(unsigned array, bit width) in an order-preserving encoding: signed
    ints bias by the sign bit, floats use the total-order bit trick (NaNs
    sort to the extremes, matching lax.sort's totalorder comparator)."""
    dt = x.dtype
    if dt == jnp.bool_:
        return x, 1  # 0/1 — one bit in the packed word
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return x, dt.itemsize * 8
    w = dt.itemsize * 8
    u = _UINT_OF[dt.itemsize]
    if jnp.issubdtype(dt, jnp.floating):
        # canonicalize before the bitcast so equality matches value
        # semantics: -0.0 groups with +0.0, and every NaN payload collapses
        # to one key (pandas-style: NaNs form a single group)
        x = jnp.where(x == 0, jnp.zeros((), dt), x)
        x = jnp.where(jnp.isnan(x), jnp.full((), jnp.nan, dt), x)
        bits = jax.lax.bitcast_convert_type(x, u)
        top = jnp.asarray(1 << (w - 1), u)
        neg = (bits >> jnp.asarray(w - 1, u)) == 1
        return jnp.where(neg, ~bits, bits | top), w
    bits = jax.lax.bitcast_convert_type(x, u)
    top = jnp.asarray(1 << (w - 1), u)
    if jnp.issubdtype(dt, jnp.signedinteger):
        return bits ^ top, w
    raise TypeError(f"unsupported operand dtype {dt}")


def pack_operands(operands: Sequence[jax.Array]) -> List[jax.Array]:
    """Greedily bit-pack the operands' order-preserving unsigned encodings
    into uint32 words (fields MSB-first within a word): lexicographic
    order AND rowwise equality over the packed words equal those over the
    original operand list, while the sort carries fewer arrays and
    comparisons.  E.g. [pad bool, validity bool, i16 key] packs to one
    18-bit-in-u32 word, so the sort carries 1 operand instead of 3.  64-bit
    fields (i64/f64 data, packed string words) pass through as standalone
    u64 operands — the 32-bit word target keeps narrow-mode programs free
    of emulated 64-bit arrays for 32-bit data."""
    return _pack_encoded([_ordered_unsigned(op) for op in operands])


def _pack_encoded(enc: Sequence[Tuple[jax.Array, int]]) -> List[jax.Array]:
    out: List[jax.Array] = []
    cur = None
    used = 0

    def flush():
        nonlocal cur, used
        if cur is not None:
            out.append(cur)
        cur, used = None, 0

    for bits, w in enc:
        if w >= 64:
            flush()
            out.append(bits)
            continue
        b32 = bits.astype(jnp.uint32)
        if cur is None or used + w > 32:
            flush()
            cur, used = b32, w
        else:
            cur = (cur << jnp.uint32(w)) | b32
            used += w
    flush()
    return out


def row_lanes(buffer: jax.Array) -> int:
    """32-bit lanes one row of ``buffer`` fills."""
    row_bytes = buffer.dtype.itemsize * math.prod(buffer.shape[1:])
    return -(-row_bytes // 4)


def pack_bits(flags: Sequence[jax.Array]) -> List[jax.Array]:
    """1-D ``bool`` buffers as bits of ``uint32`` words, 32 to a word: flag
    ``i`` is bit ``i % 32`` of word ``i // 32`` (``unpack_bit`` reads it).
    The one packer of validity vectors: a sort's payload and a join's takes
    move a word where they would move 32 ``pred`` lanes."""
    words = []
    for at in range(0, len(flags), 32):
        word = jnp.zeros(flags[at].shape, jnp.uint32)
        for bit, flag in enumerate(flags[at:at + 32]):
            word = word | (flag.astype(jnp.uint32) << jnp.uint32(bit))
        words.append(word)
    return words


def unpack_bit(words: Sequence[jax.Array], i: int) -> jax.Array:
    """Flag ``i`` of ``pack_bits``' words, wherever the words' rows went."""
    return (words[i // 32] >> jnp.uint32(i % 32)) & 1 != 0


def pack_payload(buffers: Sequence[jax.Array]):
    """Payload operands for ``lexsort_indices`` that carry ``buffers`` (each
    of the sort's ``capacity`` rows) through the sort, so that no index
    vector is built for them.  Returns ``(lanes, layout)``; ``layout`` is
    for ``unpack_payload`` and holds ``None`` for a buffer that cannot ride
    and is left to ``take(perm)``: a 2-D byte matrix and whatever is past
    ``compact.MAX_PAYLOAD_LANES``.

    1-D ``bool`` buffers (validity) ride as one bit each, 32 to a ``uint32``
    word (``pack_bits``); every other 1-D buffer rides as it is: a sort
    moves a non-key operand as bits, so NaN payloads, -0.0 and float64
    (emulated on a TPU) come back exact."""
    budget = compact.MAX_PAYLOAD_LANES
    flat = [i for i, b in enumerate(buffers) if b.ndim == 1]
    bits = [i for i in flat if buffers[i].dtype == jnp.bool_][:32 * budget]
    layout: list = [None] * len(buffers)
    lanes: List[jax.Array] = pack_bits([buffers[i] for i in bits])
    budget -= len(lanes)
    for at, i in enumerate(bits):
        layout[i] = (at // 32, at % 32)
    for i in flat:
        if layout[i] is None and buffers[i].dtype != jnp.bool_ \
                and row_lanes(buffers[i]) <= budget:
            budget -= row_lanes(buffers[i])
            layout[i] = (len(lanes), None)
            lanes.append(buffers[i])
    rode = sum(row_lanes(lane) for lane in lanes)
    obs_metrics.counter_add("sort.payload_lanes", rode)
    obs_metrics.counter_add("sort.take_lanes", sum(
        row_lanes(b) for b, where in zip(buffers, layout) if where is None))
    return lanes, tuple(layout)


def unpack_payload(sorted_lanes: Sequence[jax.Array], layout) -> list:
    """The buffers ``pack_payload`` packed, in sorted order; ``None`` where
    the layout holds ``None``.  ``sorted_lanes`` is indexed by lane: a
    mapping will do where only some lanes were moved."""
    out = []
    for where in layout:
        if where is None:
            out.append(None)
            continue
        lane, bit = where
        out.append(sorted_lanes[lane] if bit is None else
                   unpack_bit(sorted_lanes, 32 * lane + bit))
    return out


def compact_columns(mask: jax.Array, cols: Sequence[Column]):
    """``(columns, count)``: the rows of ``cols`` (each of ``mask``'s
    capacity) where ``mask`` is True, packed to the front in order, null
    past the new ``count``.  What ``compact.compact_indices(mask)`` and
    ``Column.take(idx, valid_mask=live_mask(cap, count))`` of every column
    give, bit for bit, with no index between them: the buffers ride the
    compaction as its payload (``pack_payload``), and only a buffer that
    cannot ride is taken through the index."""
    buffers, columns = jax.tree.flatten(tuple(cols))
    with stage("compact.permute"):
        lanes, layout = pack_payload(buffers)
    idx, count, *carried = compact.compact_indices(mask, *lanes)
    with stage("compact.permute"):
        moved = jax.tree.unflatten(columns, [
            jnp.take(buffer, idx, axis=0, mode="clip") if rode is None
            else rode
            for buffer, rode in zip(buffers, unpack_payload(carried, layout))])
        live = compact.live_mask(mask.shape[0], count)
        return tuple(c.masked(live) for c in moved), count


def lexsort_indices(operands: Sequence[jax.Array], capacity: int,
                    payload: Sequence[jax.Array] = (),
                    ) -> Tuple[jax.Array, List[jax.Array], List[jax.Array]]:
    """Stable lexicographic argsort over bit-packed operands.  Returns
    (permutation, sorted PACKED operands, sorted payload) — the packed
    words support adjacency/equality tests (rows_equal_adjacent,
    dense_group_ids) but not per-field access.  A buffer whose rows are
    needed in sorted order rides a sort as ``payload``: 1-D arrays of
    ``capacity`` rows (``pack_payload`` builds them), never compared,
    returned as ``take(x, permutation)`` would return them with no gather.

    Fast path: when every key field plus a row index fits 64 bits (e.g.
    padding + validity + a 32-bit key + up to 30 index bits — the
    hash-partitioned join/groupby shape), the sort runs over one or two
    u32 words with the index in the low bits: no index operand, and
    uniqueness makes stability free; payload is operands 2... of the same
    sort.  The words stay 32-bit — narrow mode's zero-64-bit-arrays
    guarantee holds (64-bit ops are emulated on TPU).

    General path: a stable sort of the packed words and a row index, as
    without payload; the payload then rides a sort of its own, keyed on
    the rows' ranks (``_ride_ranks``).  A lane added to the many-word
    stable sort costs the chip's compiler several times what it costs in
    a one-key sort (PERF.md Findings PR 26)."""
    payload = tuple(payload)
    enc = [_ordered_unsigned(o) for o in operands]
    total_bits = sum(w for _, w in enc)
    idx_bits = compact.index_bits(capacity)
    if total_bits + idx_bits <= 64:
        # assemble the logical (total+idx)-bit value MSB-first across
        # (hi, lo) u32 words with static double-word shifts
        hi = jnp.zeros((capacity,), jnp.uint32)
        lo = jnp.zeros((capacity,), jnp.uint32)

        def append(bits_u32, w: int):
            nonlocal hi, lo
            if w == 32:
                hi, lo = lo, bits_u32
            else:
                hi = (hi << jnp.uint32(w)) | (lo >> jnp.uint32(32 - w))
                lo = (lo << jnp.uint32(w)) | bits_u32

        for bits, w in enc:
            append(bits.astype(jnp.uint32), w)
        append(jnp.arange(capacity, dtype=jnp.uint32), idx_bits)

        words = (lo,) if total_bits + idx_bits <= 32 else (hi, lo)
        index_mask = jnp.uint32((1 << idx_bits) - 1)
        # keys are unique: no stability needed
        sorted_all = jax.lax.sort(words + payload, num_keys=len(words),
                                  is_stable=False)
        s_hi, s_lo = sorted_all[0], sorted_all[len(words) - 1]
        perm = (s_lo & index_mask).astype(jnp.int32)
        moved = list(sorted_all[len(words):])
        s_lo = s_lo >> jnp.uint32(idx_bits)
        return perm, ([s_lo] if len(words) == 1 else [s_hi, s_lo]), moved
    packed = _pack_encoded(enc)
    if len(packed) > _MAX_KEY_WORDS:
        perm = _word_by_word_argsort(packed, capacity)
        return (perm, [jnp.take(word, perm) for word in packed],
                _ride_ranks(perm, payload))
    iota = jnp.arange(capacity, dtype=jnp.int32)
    sorted_all = jax.lax.sort(tuple(packed) + (iota,),
                              num_keys=len(packed), is_stable=True)
    perm = sorted_all[-1]
    return perm, list(sorted_all[:-1]), _ride_ranks(perm, payload)


#: Most packed key words one stable sort compares (two 64-bit keys and
#: their flags are four).  Past it (string keys: a 32-byte string is four
#: 64-bit words) the sort goes a word at a time.  The
#: chip's compiler takes 4.8 / 19 / 108 s for a stable sort of 1 / 3 / 9
#: ``u32`` keys at 2^14 rows and twelve times that at 393,216 (described
#: v5e, PERF.md Findings PR 31: Q5's group-by on ``n_name`` compiled in
#: 578 s); the loop below compiles one one-key sort whatever the words.
_MAX_KEY_WORDS = 4


def _word_by_word_argsort(packed: Sequence[jax.Array],
                          capacity: int) -> jax.Array:
    """The stable lexicographic argsort of ``packed`` as one stable one-key
    sort a 32-bit word, least significant first, each word taken through
    the order so far: a loop whose body holds one sort.  It pays a gather a
    word, so it is for keys too long to compare in one sort."""
    words = []
    for word in packed:
        if word.dtype.itemsize == 8:
            words += [(word >> jnp.asarray(32, word.dtype)).astype(jnp.uint32),
                      word.astype(jnp.uint32)]
        else:
            words.append(word.astype(jnp.uint32))
    stacked = jnp.stack(words)

    def one_word(i, perm):
        word = jax.lax.dynamic_index_in_dim(stacked, len(words) - 1 - i,
                                            keepdims=False)
        return jax.lax.sort((jnp.take(word, perm), perm), num_keys=1,
                            is_stable=True)[1]

    return jax.lax.fori_loop(0, len(words), one_word,
                             jnp.arange(capacity, dtype=jnp.int32))


def _ride_ranks(perm: jax.Array, payload: Tuple[jax.Array, ...]):
    """``[take(x, perm) for x in payload]`` by two one-key sorts and no
    gather: ``perm`` sorted with a row index gives each row its rank (the
    inverse permutation), and the payload rides one unstable sort keyed on
    the ranks (the shape of ``compact.inverse_permute``)."""
    if not payload:
        return []
    iota = jnp.arange(perm.shape[0], dtype=jnp.int32)
    _, rank = jax.lax.sort((perm, iota), num_keys=1, is_stable=False)
    return list(jax.lax.sort((rank,) + payload, num_keys=1,
                             is_stable=False)[1:])


def rows_equal_adjacent(sorted_operands: Sequence[jax.Array]) -> jax.Array:
    """bool[n]: row i has identical key to row i-1 (row 0 -> False).

    Operand 0 is the padding flag, which participates: a padding row never
    equals a live row, while padding rows equal each other (harmless — they
    are masked out downstream)."""
    eq = None
    for op in sorted_operands:
        e = jnp.concatenate([jnp.zeros((1,), bool), op[1:] == op[:-1]])
        eq = e if eq is None else (eq & e)
    return eq


def dense_group_ids(sorted_operands: Sequence[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Dense group ids over sorted rows: (group_id[n], num_groups_incl_padding).

    group_id is 0-based and nondecreasing along the sorted order; rows with
    equal keys share an id.  ``num_groups`` counts all distinct keys present
    including the single padding group when padding rows exist; callers mask
    with the live-row count."""
    eq = rows_equal_adjacent(sorted_operands)
    new_group = ~eq
    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    num = gid[-1] + 1 if gid.shape[0] else jnp.zeros((), jnp.int32)
    return gid, num
