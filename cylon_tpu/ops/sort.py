"""Local multi-column sort.

Replaces the reference's index-sort kernels (cpp/src/cylon/arrow/
arrow_kernels.hpp:180-314 NumericIndexSortKernel / SortIndicesInPlace,
util/arrow_utils.cpp SortTable) with one fused ``jax.lax.sort`` over
lexicographic key operands + a gather.  Padding rows always sort last, so
the dynamic row count is unchanged.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..column import Column
from ..obs import stage
from . import keys


def sort_rows(cols: Tuple[Column, ...], count, by: Sequence[int],
              ascending: Sequence[bool] | None = None,
              nulls_first: bool = True) -> Tuple[Tuple[Column, ...], object]:
    """Sort all columns by the key columns ``by``; returns (columns, count).

    Called eagerly on a one-shard table, this is one program for the
    permutation and one for each buffer it moves, not one for all: on a
    v5e the takes of eight 2^24-row buffers run 2.4 times faster apart
    than compiled into one program (PERF.md, PR 25)."""
    if ascending is None:
        ascending = [True] * len(by)
    perm = _sort_permutation(tuple(cols[i] for i in by), count,
                             tuple(ascending), nulls_first)
    return jax.tree.map(lambda buffer: _take_rows(buffer, perm), cols), count


@partial(jax.jit, static_argnames=("ascending", "nulls_first"))
@stage("sort.keys")
def _sort_permutation(key_cols, count, ascending, nulls_first):
    cap = key_cols[0].data.shape[0]
    operands = keys.build_operands(key_cols, count, cap, ascending=ascending,
                                   nulls_first=nulls_first)
    return keys.lexsort_indices(operands, cap)[0]


@jax.jit
@stage("sort.permute")
def _take_rows(buffer, perm):
    """``Column.take`` of one buffer, with no mask."""
    return jnp.take(buffer, perm, axis=0, mode="clip")
