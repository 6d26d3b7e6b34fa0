"""Local unique / drop-duplicates.

TPU-native replacement for the reference's hash-set unique
(cpp/src/cylon/table.cpp:966-1029 — bytell hash-set insert per row building
a keep-filter, with 'first'/'last' keep semantics).  Here: lexsort the key
columns; the sort is stable (or embeds the row index in the key word), so
rows inside a key run sit in original row order and each run's first/last
position IS the group's first/last occurrence — one scatter along the
permutation marks the kept rows, then a compaction restores original
order like the reference's filter does.  No segment min/max needed.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..column import Column
from . import compact, keys


@partial(jax.jit, static_argnames=("key_idx", "keep"))
def unique(cols: Tuple[Column, ...], count, key_idx: Tuple[int, ...],
           keep: str = "first"):
    """Returns (columns, new_count): rows with a duplicate key removed,
    keeping the first or last occurrence, original order preserved."""
    if keep not in ("first", "last"):
        raise ValueError(f"keep must be 'first' or 'last', got {keep!r}")
    cap = cols[0].data.shape[0]
    key_cols = [cols[i] for i in key_idx]
    operands = keys.build_operands(key_cols, count, cap)
    perm, sorted_ops, _ = keys.lexsort_indices(operands, cap)
    live_sorted = jnp.arange(cap, dtype=jnp.int32) < count

    new_group = ~keys.rows_equal_adjacent(sorted_ops)
    if keep == "first":
        rep_pos = new_group  # run start = smallest original index in the run
    else:  # run end = largest original index in the run
        rep_pos = jnp.concatenate([new_group[1:], jnp.ones((1,), bool)])
    leader = rep_pos & live_sorted  # padding runs sort last -> excluded

    # leader flags travel back to original row order along the (full)
    # sort permutation — fused key-sort on TPU, scatter elsewhere
    keep_mask = compact.inverse_permute(
        perm, leader.astype(jnp.int32))[0] == 1

    perm_keep, m = compact.compact_indices(keep_mask)
    out = tuple(c.take(perm_keep, valid_mask=compact.live_mask(cap, m)) for c in cols)
    return out, m
