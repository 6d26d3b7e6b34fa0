"""Local multi-column sort.

Replaces the reference's index-sort kernels (cpp/src/cylon/arrow/
arrow_kernels.hpp:180-314 NumericIndexSortKernel / SortIndicesInPlace,
util/arrow_utils.cpp SortTable) with one fused ``jax.lax.sort`` over
lexicographic key operands that carries the table's buffers as payload
operands: the rows ride the sort and come back sorted, and no index vector
is built for them.  Only what cannot ride (a string column's byte matrix,
a table wider than the sort's lane budget) is gathered through the
permutation.  Padding rows always sort last, so the dynamic row count is
unchanged.
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

    One program sorts the keys with every buffer that can ride as payload
    (``keys.pack_payload``).  A buffer that cannot is taken through the
    permutation by a program of its own: called eagerly on a one-shard
    table, takes compiled into one program ran 2.4 times slower on a v5e
    than a program a buffer (PERF.md, PR 25)."""
    if ascending is None:
        ascending = [True] * len(by)
    buffers, columns = jax.tree.flatten(cols)
    perm, moved = _sort_carrying(cols, count, tuple(by), tuple(ascending),
                                 nulls_first)
    return jax.tree.unflatten(columns, [
        _take_rows(buffer, perm) if rode is None else rode
        for buffer, rode in zip(buffers, moved)]), count


@partial(jax.jit, static_argnames=("by", "ascending", "nulls_first"))
def _sort_carrying(cols, count, by, ascending, nulls_first):
    """(perm, buffers of ``cols`` in sorted order): ``None`` for a buffer
    that did not ride, and for ``perm`` where every buffer did."""
    cap = cols[0].data.shape[0]
    with stage("sort.permute"):
        lanes, layout = keys.pack_payload(jax.tree.leaves(cols))
    with stage("sort.keys"):
        operands = keys.build_operands([cols[i] for i in by], count, cap,
                                       ascending=ascending,
                                       nulls_first=nulls_first)
        perm, _, lanes = keys.lexsort_indices(operands, cap, lanes)
    with stage("sort.permute"):
        return (perm if None in layout else None,
                keys.unpack_payload(lanes, layout))


@jax.jit
@stage("sort.permute")
def _take_rows(buffer, perm):
    """``Column.take`` of one buffer, with no mask."""
    return jnp.take(buffer, perm, axis=0, mode="clip")
