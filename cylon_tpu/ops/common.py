"""Shared helpers for multi-table kernels."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..column import Column
from . import keys


def widen_strings(a: Column, b: Column) -> Tuple[Column, Column]:
    """Pad two string columns' byte matrices to a common width so they can be
    concatenated / compared (zero padding preserves order)."""
    if not a.is_string:
        return a, b
    wa, wb = a.string_width, b.string_width
    w = max(wa, wb)

    def pad(c: Column) -> Column:
        if c.string_width == w:
            return c
        extra = jnp.zeros((c.data.shape[0], w - c.string_width), jnp.uint8)
        return Column(jnp.concatenate([c.data, extra], axis=1), c.validity,
                      c.lengths, c.dtype)

    return pad(a), pad(b)


def concat_columns(a: Column, b: Column) -> Column:
    """Stack two columns' buffers (paddings and all) into one column of
    capacity cap_a + cap_b."""
    a, b = widen_strings(a, b)
    data = jnp.concatenate([a.data, b.data], axis=0)
    validity = jnp.concatenate([a.validity, b.validity])
    lengths = None
    if a.lengths is not None:
        lengths = jnp.concatenate([a.lengths, b.lengths])
    return Column(data, validity, lengths, a.dtype)


def two_table_padding(cap_a: int, count_a, cap_b: int, count_b) -> jax.Array:
    """Padding-flag operand (bool — one packed bit) for a concatenated pair
    of tables."""
    idx = jnp.arange(cap_a + cap_b, dtype=jnp.int32)
    in_a = idx < cap_a
    pad_a = idx >= count_a
    pad_b = (idx - cap_a) >= count_b
    return jnp.where(in_a, pad_a, pad_b)


def combined_sorted_runs(cols_a: Sequence[Column], count_a,
                         cols_b: Sequence[Column], count_b,
                         key_a: Sequence[int], key_b: Sequence[int]):
    """Lexsort the union of two tables' key rows and mark the key runs.

    This is the TPU replacement for the reference's hash-table row matching
    (HashJoinKernel build/probe, arrow/arrow_hash_kernels.hpp:33-215, and the
    RowComparator hash-sets of the set ops, table.cpp:522-734): after one
    fused multi-key sort of all rows from both tables, rows with equal keys
    are one contiguous run, turning every equality problem downstream into
    prefix arithmetic over the sorted order (segments.run_extents) — no
    group-id arrays, no scatters.

    Returns (perm, sorted_ops, new_group, is_run_end, live_sorted) over the
    cap_a + cap_b sorted positions; ``perm[p] < cap_a`` identifies table-A
    rows, and padding rows from either table sort last (the padding flag is
    the primary sort operand), so ``live_sorted`` is a prefix mask.
    """
    cap_a = cols_a[0].data.shape[0]
    cap_b = cols_b[0].data.shape[0]
    n = cap_a + cap_b
    operands: List[jax.Array] = [two_table_padding(cap_a, count_a, cap_b, count_b)]
    for ia, ib in zip(key_a, key_b):
        combined = concat_columns(cols_a[ia], cols_b[ib])
        operands.extend(keys.column_operands(combined))
    perm, sorted_ops, _ = keys.lexsort_indices(operands, n)
    new_group = ~keys.rows_equal_adjacent(sorted_ops)
    is_run_end = jnp.concatenate([new_group[1:], jnp.ones((1,), bool)])
    pos = jnp.arange(n, dtype=jnp.int32)
    live_sorted = pos < (count_a + count_b)
    return perm, sorted_ops, new_group, is_run_end, live_sorted
