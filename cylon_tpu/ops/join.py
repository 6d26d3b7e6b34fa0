"""Local join kernel (sort-merge over dense key ids).

TPU-native replacement for the reference's local join layer
(cpp/src/cylon/join/join.cpp:31-763: type-dispatched sort-merge and
``std::unordered_multimap`` hash joins; arrow/arrow_hash_kernels.hpp
build/probe; join_utils.cpp build_final_table).  Design:

1. One fused multi-key ``lax.sort`` over the union of both tables' key rows
   — the kernel's ONLY sort — subsumes both the comparator machinery and
   the hash table, works for any column type mix, and has no
   data-dependent control flow.
2. Per-left-row match ranges [lo, lo+matches) into the key-ordered right
   side are prefix arithmetic over the sorted order (cumsum + segmented
   broadcasts); the key-ordered right permutation is the combined sort's
   permutation carried by a partition of its right entries (one one-word
   sort it rides as payload on a TPU, ``compact.partition_indices``) —
   the merge step without a second key sort and without an index.
3. The variable-size expansion (a left row with k matches emits k rows;
   outer variants emit null-filled singletons, the reference's -1 fills,
   join.cpp:179-235) is realized as a static-capacity gather: each emitting
   row scatters its index at its first output slot and a ``cummax`` forward
   fill maps every slot back to its (left row, match ordinal) — one scan,
   no sort.  Two lanes go through an index for it (``_expansion``).
4. The output takes move a side's rows through its slot -> row index: one
   take a data buffer, and the side's validity vectors as bits of one
   ``uint32`` word for 32 columns (``take_side``).  The cell's ``(int64,
   float64)`` sides cost 13 lanes through an index in all; a lane is the
   unit of cost on a TPU (0.145 s for 2^24 rows, PERF.md §6).

Everything is a static-shape XLA program; the only dynamic quantity is the
returned row count.  ``join_row_count`` exposes the exact output size so the
host can pick (and cache) an output capacity before running ``join_gather``:
``_indices`` (steps 1-3) and a ``take_side`` a side (step 4) in ONE
program.  A take among the join's others costs what it costs in a program
of its own, once the compiler can keep the table it reads in its fast
memory space (PERF.md §6, PR 30: the split was measured and is not
shipped); ``join_indices`` is steps 1-3 alone, the seam for a caller that
would materialize a column when it is read.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..column import Column
from ..config import JoinType
from ..obs import stage
from . import common, compact, keys, segments

_I32_MAX = jnp.iinfo(jnp.int32).max
#: ``_expansion``'s ``delta`` of a left row that matches nothing
_NO_MATCH = jnp.iinfo(jnp.int32).min


def _match_ranges(cols_l, count_l, cols_r, count_r, left_on, right_on,
                  join_type: JoinType):
    """Compute per-left-row match ranges into a gid-ordered right table.

    One fused multi-key ``lax.sort`` over the union of both tables' key rows
    is the ONLY sort in the kernel (the reference's hash build/probe,
    join.cpp:448-513, and its comparator sorts, join.cpp:78-434, both
    collapse into it).  Everything else is prefix arithmetic over the
    sorted order:

    - a left row's match range [lo, lo+matches) = (# live right rows before
      its key run, # live right rows inside it) — cumsum + segmented
      broadcast (cummax of run-start values / suffix-cummin of run-end
      values), replacing per-gid histogram scatter-adds;
    - the gid-ordered right permutation falls out of the combined sort by
      partitioning its right-side entries to the front, the permutation
      riding along (``compact.partition_indices``) — no second key sort;
    - per-original-row results come back through one scatter along the sort
      permutation.

    Returns (lo, matches, perm_r, live_l, unmatched_right_mask,
    left_key_order) where left_key_order lists left row ids in key order
    (used by key_grouped join output to avoid another sort).
    """
    cap_l = cols_l[0].data.shape[0]
    cap_r = cols_r[0].data.shape[0]
    perm, _, new_group, is_run_end, live_sorted = common.combined_sorted_runs(
        cols_l, count_l, cols_r, count_r, left_on, right_on)
    is_right = perm >= cap_l

    # live right rows before / inside each position's key run
    lo_sorted, matches_sorted = segments.run_extents(
        is_right & live_sorted, new_group, is_run_end)

    fields = [lo_sorted, matches_sorted]
    if join_type in (JoinType.RIGHT, JoinType.FULL_OUTER):
        _, left_in_run = segments.run_extents(
            (~is_right) & live_sorted, new_group, is_run_end)
        fields.append((left_in_run == 0).astype(jnp.int32))

    # map per-sorted-position results back to original rows: one fused
    # key-sort on TPU, one scatter per field elsewhere (compact.permute_mode)
    back = compact.inverse_permute(perm, *fields)

    live_l = jnp.arange(cap_l, dtype=jnp.int32) < count_l
    live_r = jnp.arange(cap_r, dtype=jnp.int32) < count_r
    lo = back[0][:cap_l]
    matches = jnp.where(live_l, back[1][:cap_l], 0)
    if join_type in (JoinType.RIGHT, JoinType.FULL_OUTER):
        unmatched_r = live_r & (back[2][cap_l:] == 1)
    else:
        unmatched_r = jnp.zeros((cap_r,), bool)

    # gid-ordered right permutation AND left key order from ONE stable
    # partition of the combined sort's entries, which ``perm`` rides
    # (compact.py: no index is built to move it): exactly cap_r of them
    # are right-side (perm is a full permutation), so the front cap_r
    # slots are the right rows in key order (the order ``lo`` indexes
    # into) and the tail cap_l slots are the left rows in key order
    # (key_grouped output)
    _, _, by_side = compact.partition_indices(is_right, perm)
    return (lo, matches, by_side[:cap_r] - cap_l, live_l, unmatched_r,
            by_side[cap_r:])


@stage("join.emit")
def _emission(matches, live_l, join_type: JoinType):
    emit = matches
    if join_type in (JoinType.LEFT, JoinType.FULL_OUTER):
        emit = jnp.where(live_l & (matches == 0), jnp.int32(1), matches)
    csum = jnp.cumsum(emit, dtype=jnp.int32)
    total = csum[-1] if emit.shape[0] else jnp.zeros((), jnp.int32)
    return emit, csum, total


@stage("join.ranges")
def _ranges(cols_l, count_l, cols_r, count_r, left_on, right_on, join_type,
            algorithm: str):
    if algorithm == "hash":
        from . import hash_join

        return hash_join.match_ranges_hash(
            cols_l, count_l, cols_r, count_r, left_on, right_on,
            join_type) + (None,)
    return _match_ranges(cols_l, count_l, cols_r, count_r, left_on, right_on,
                         join_type)


@stage("join.expand")
def _key_grouped_order(lo, matches, left_key_order):
    """Left rows in key order with the matched ones front-packed: (lo,
    matches) reordered, and the permutation that did it.  ``matches`` is 0
    on a dead row, so it says which rows are matched by itself."""
    cap_l = lo.shape[0]
    if left_key_order is None:  # hash path: order by match-range offset
        order_key = jnp.where(matches > 0, lo, _I32_MAX)
        iota_l = jnp.arange(cap_l, dtype=jnp.int32)
        _, perm_l = jax.lax.sort((order_key, iota_l), num_keys=1,
                                 is_stable=True)
    else:  # sort path: key order is known; partition matched to front
        part, _ = compact.partition_indices(
            jnp.take(matches, left_key_order) > 0)
        perm_l = jnp.take(left_key_order, part)
    return jnp.take(lo, perm_l), jnp.take(matches, perm_l), perm_l


@stage("join.expand")
def _expansion(lo, matches, perm_r, unmatched_r, perm_l, emit, csum, total,
               join_type: JoinType, out_capacity: int):
    """Output slot -> (left row, right row): (lidx, ridx, lvalid, rvalid,
    out_count) over ``out_capacity`` slots.

    Two lanes go through an index here, whatever the join type: what slot
    ``k`` needs of its left row ``li`` is one ``int32``, ``delta = lo -
    base_l`` (its position among the key-ordered right rows is ``k +
    delta[li]``), and ``delta`` can say "no match" as well, because both
    terms lie in ``[0, 2^31)`` and so ``INT32_MIN`` is free; the position
    then reads the right row off one table, the key-ordered right rows
    with the unmatched ones (RIGHT / FULL_OUTER's tail) behind them."""
    k = jnp.arange(out_capacity, dtype=jnp.int32)
    cap_l = emit.shape[0]
    cap_r = perm_r.shape[0]
    base_l = csum - emit
    if compact.permute_mode() == "sort":
        # slot -> left row is searchsorted(csum, k, 'right') — csum is
        # monotone, so slot k's emitter is the count of rows with
        # csum <= k.  Realized as a sort-merge (sorts beat scatters on
        # TPU; see compact.count_leq_dense).
        li = compact.count_leq_dense(csum, out_capacity)
    else:
        # scatter + cummax forward fill: each emitting row drops its index
        # at its first output slot (bases are distinct and ascending),
        # cummax fills the run — one scan, one scatter
        iota_l = jnp.arange(cap_l, dtype=jnp.int32)
        marker = jnp.full((out_capacity,), -1, jnp.int32)
        marker = marker.at[jnp.where(emit > 0, base_l, out_capacity)].max(
            iota_l, mode="drop")
        li = jax.lax.cummax(marker)
    li = jnp.clip(li, 0, cap_l - 1)
    delta = jnp.take(jnp.where(matches > 0, lo - base_l, _NO_MATCH), li)

    in_main = k < total
    lvalid = in_main
    rvalid = in_main & (delta != _NO_MATCH)
    lidx = li if perm_l is None else jnp.take(perm_l, li)
    pos = jnp.where(rvalid, jnp.clip(k + delta, 0, cap_r - 1), 0)
    right_rows = perm_r

    out_count = total
    if join_type in (JoinType.RIGHT, JoinType.FULL_OUTER):
        perm_u, m = compact.compact_indices(unmatched_r)
        tail = k - total
        in_tail = (k >= total) & (tail < m)
        pos = jnp.where(in_tail, cap_r + jnp.clip(tail, 0, cap_r - 1), pos)
        right_rows = jnp.concatenate([perm_r, perm_u])
        rvalid = rvalid | in_tail
        lvalid = lvalid & ~in_tail
        out_count = total + m
    ridx = jnp.where(rvalid, jnp.take(right_rows, pos), 0)
    return lidx, ridx, lvalid, rvalid, out_count


@partial(jax.jit, static_argnames=("left_on", "right_on", "join_type",
                                   "algorithm"))
def join_row_count(cols_l: Tuple[Column, ...], count_l,
                   cols_r: Tuple[Column, ...], count_r,
                   left_on: Tuple[int, ...], right_on: Tuple[int, ...],
                   join_type: JoinType, algorithm: str = "sort"):
    """Exact output row count of the join (device scalar)."""
    lo, matches, perm_r, live_l, unmatched_r, _ = _ranges(
        cols_l, count_l, cols_r, count_r, left_on, right_on, join_type,
        algorithm)
    _, _, total = _emission(matches, live_l, join_type)
    if join_type in (JoinType.RIGHT, JoinType.FULL_OUTER):
        total = total + jnp.sum(unmatched_r, dtype=jnp.int32)
    return total


def _indices(cols_l: Tuple[Column, ...], count_l,
             cols_r: Tuple[Column, ...], count_r,
             left_on: Tuple[int, ...], right_on: Tuple[int, ...],
             join_type: JoinType, out_capacity: int,
             algorithm: str = "sort", key_grouped: bool = False):
    """The join up to its output takes: ``(lidx, ridx, lvalid, rvalid,
    out_count)`` — for each of ``out_capacity`` slots the left and the right
    row it holds and whether it holds one (an outer join's fill holds none
    on one side), and the dynamic output row count.  ``take_side`` turns a
    side's columns and these into output columns; ``join_gather`` is both."""
    lo, matches, perm_r, live_l, unmatched_r, left_key_order = _ranges(
        cols_l, count_l, cols_r, count_r, left_on, right_on, join_type,
        algorithm)
    perm_l = None
    if key_grouped:
        if join_type != JoinType.INNER:
            raise ValueError("key_grouped join output requires INNER")
        lo, matches, perm_l = _key_grouped_order(lo, matches, left_key_order)
        live_l = None  # in the old order, and INNER's emission reads none
    emit, csum, total = _emission(matches, live_l, join_type)
    return _expansion(lo, matches, perm_r, unmatched_r, perm_l, emit, csum,
                      total, join_type, out_capacity)


join_indices = jax.jit(_indices, static_argnames=(
    "left_on", "right_on", "join_type", "out_capacity", "algorithm",
    "key_grouped"))


def take_side(cols: Sequence[Column], idx, slot_valid,
              side: str) -> Tuple[Column, ...]:
    """``[c.take(idx, valid_mask=slot_valid) for c in cols]``, bit for bit,
    with no ``pred`` vector through the index: the side's validity vectors
    go as bits of one ``uint32`` word for 32 columns (``keys.pack_bits``),
    ``take_lanes(cols)`` lanes in all.  ``side`` is ``"left"`` or
    ``"right"``, the stage the device time is booked to."""
    with stage(f"join.gather_{side}"):
        def take(buffer):
            return jnp.take(buffer, idx, axis=0, mode="clip")

        words = [take(w) for w in keys.pack_bits([c.validity for c in cols])]
        out = []
        for i, c in enumerate(cols):
            # a slot that holds no row of the side, or a null, reads zero
            valid = keys.unpack_bit(words, i) & slot_valid
            data = jnp.where(valid if c.data.ndim == 1 else valid[:, None],
                             take(c.data), jnp.zeros((), c.data.dtype))
            lengths = None if c.lengths is None else jnp.where(
                valid, take(c.lengths), 0)
            out.append(Column(data, valid, lengths, c.dtype))
        return tuple(out)


def take_lanes(cols: Sequence[Column]) -> int:
    """32-bit lanes ``take_side`` sends through the index for ``cols``."""
    return -(-len(cols) // 32) + sum(
        keys.row_lanes(buffer) for c in cols for buffer in (c.data, c.lengths)
        if buffer is not None)


@partial(jax.jit, static_argnames=("left_on", "right_on", "join_type",
                                   "out_capacity", "algorithm",
                                   "key_grouped", "project"))
def join_gather(cols_l: Tuple[Column, ...], count_l,
                cols_r: Tuple[Column, ...], count_r,
                left_on: Tuple[int, ...], right_on: Tuple[int, ...],
                join_type: JoinType, out_capacity: int,
                algorithm: str = "sort", key_grouped: bool = False,
                project: "Tuple[int, ...] | None" = None):
    """Produce gathered output columns (left columns ++ right columns) with
    capacity ``out_capacity`` and the dynamic output row count: ``_indices``
    and a ``take_side`` a side in one program.

    ``key_grouped=True`` (INNER only): rows with equal join keys come out
    adjacent, so a downstream group-by on the key can use the boundary-scan
    pipeline kernel instead of re-sorting the whole output.  Grouping
    reorders left rows into key order — on the sort path that order falls
    out of the combined lexsort (left_key_order) and matched rows are
    front-packed with one stable partition (no extra sort); the hash path
    has no key-sorted order, so it sorts left rows by their match-range
    offset ``lo``, which uniquely identifies the key group for matched
    rows.  Either way the multi-operand lexsort of the (larger) join
    output downstream is saved."""
    lidx, ridx, lvalid, rvalid, out_count = _indices(
        cols_l, count_l, cols_r, count_r, left_on, right_on, join_type,
        out_capacity, algorithm, key_grouped)

    # projection pushdown: materialize ONLY the requested output columns
    # (indices into left ++ right), in the requested order — a pruned
    # column skips its whole out_capacity-sized gather+write (the
    # reference prunes after materializing, join_utils.cpp
    # build_final_table; here pruning happens inside the kernel)
    n_l = len(cols_l)
    n_out = n_l + len(cols_r)
    if project is None:
        project = tuple(range(n_out))
    bad = [j for j in project if not 0 <= j < n_out]
    if bad:
        raise ValueError(f"project indices {bad} out of range for "
                         f"{n_out} output columns (left {n_l} ++ right "
                         f"{n_out - n_l}; negatives not supported)")
    wanted = sorted(set(project))
    taken = dict(zip(
        wanted,
        take_side([cols_l[j] for j in wanted if j < n_l], lidx, lvalid,
                  "left")
        + take_side([cols_r[j - n_l] for j in wanted if j >= n_l], ridx,
                    rvalid, "right")))
    return tuple(taken[j] for j in project), out_count
