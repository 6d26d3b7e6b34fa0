"""Mask -> front-packed compaction.

The reference materializes filtered results via ``arrow::compute::Filter``
over boolean masks (e.g. groupby index columns, hash_groupby.cpp:135-192;
Select, table.cpp:491-520).  The static-shape XLA equivalent: a stable sort
on the inverted mask yields a permutation that packs kept rows to the front
in original order; the new dynamic row count is the mask popcount.  One fused
sort+gather instead of a dynamically-sized filter.

Two interchangeable realizations; :func:`permute_mode` says which one the
platform gets (``realization.py`` has the table and the measurements):

- ``scatter``: cumsum destinations + one permuting scatter (one linear
  pass, where a scatter is cheap: XLA:CPU).
- ``sort``: pack (mask bit above row index) into ONE u32 word and
  ``lax.sort`` it (XLA:TPU serializes scatters).

A compaction whose index exists only to move rows moves them itself:
``compact_indices(mask, *payload)``, ``partition_indices`` and the
exchange's grouping by target, ``sort_by_target``, return each 1-D payload
array as ``jnp.take(x, idx)`` would, bit for bit.  Under
``sort`` the arrays are operands 2... of the packed word's own sort (a
32-bit lane costs 15-18 ms there at 2^24 rows, 0.144-0.45 s through an
index: PERF.md §6); under ``scatter`` they go through ``jnp.take``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..obs import metrics as obs_metrics, stage
from . import realization


def permute_mode() -> str:
    """How permutations/compactions are materialized: "scatter" | "sort"."""
    return realization.current().permute


def index_bits(cap: int) -> int:
    """Bits needed to carry a row index in [0, cap) inside a packed sort
    word (shared with keys.lexsort_indices — the packing-width formula
    must stay single-sourced)."""
    return max(1, (cap - 1).bit_length()) if cap > 1 else 1


def _mask_sort_perm(mask: jax.Array, payload: Tuple[jax.Array, ...] = ()):
    """Stable partition permutation via ONE single-word unstable sort:
    ``(~mask) << idx_bits | row`` — all words unique, ascending row bits
    make the unstable sort stable per mask value.  Arrays longer than
    2^31 rows can arise internally (e.g. the join expansion's merge of
    csum + out_capacity slots), where flag+index no longer fit u32; those
    fall back to a two-operand stable sort.  Returns ``(perm, carried)``:
    ``payload`` rides either sort as further operands, never compared."""
    cap = mask.shape[0]
    bits = index_bits(cap)
    obs_metrics.counter_add("compact.payload_lanes", sum(
        -(-x.dtype.itemsize // 4) for x in payload))
    if bits + 1 > 32:
        # >=2^31 rows: int32 positions would wrap negative — exactly the
        # case this branch exists for — so carry the permutation in int64
        # (x64 is enabled package-wide; round-4 advice finding 1)
        iota = jnp.arange(cap, dtype=jnp.int64)
        _, perm, *carried = jax.lax.sort(
            (jnp.where(mask, jnp.uint32(0), jnp.uint32(1)), iota) + payload,
            num_keys=1, is_stable=True)
        return perm, carried
    iota = jnp.arange(cap, dtype=jnp.uint32)
    word = (jnp.where(mask, jnp.uint32(0), jnp.uint32(1))
            << jnp.uint32(bits)) | iota
    s, *carried = jax.lax.sort((word,) + payload, num_keys=1, is_stable=False)
    return (s & jnp.uint32((1 << bits) - 1)).astype(jnp.int32), carried


#: Most 32-bit payload lanes one sort carries beside its keys; what is past
#: it moves through ``take(perm)``.  A lane more costs every such sort
#: compile seconds as well as device time (PERF.md, Findings).
MAX_PAYLOAD_LANES = 12


@stage("compact.partition")
def sort_by_target(targets: jax.Array, world: int, *payload: jax.Array):
    """``(perm, *carried)``: the stable permutation that groups rows by
    ``targets`` (values in [0, world], ``world`` the padding last), and
    each 1-D ``payload`` array as ``jnp.take(x, perm)`` would return it.
    The small-alphabet form of ``_mask_sort_perm``: where target and row
    index fit one word, ``target << idx_bits | row`` is sorted unstably
    (the words are unique, so rows keep their order inside a target);
    past 32 bits, a two-operand stable sort.  The payload rides either
    sort as further operands, never compared."""
    cap = targets.shape[0]
    bits = index_bits(cap)
    if world.bit_length() + bits > 32:
        iota = jnp.arange(cap, dtype=_idx_dtype(cap))
        _, perm, *carried = jax.lax.sort((targets, iota) + payload,
                                         num_keys=1, is_stable=True)
        return (perm, *carried)
    word = (targets.astype(jnp.uint32) << jnp.uint32(bits)) | jnp.arange(
        cap, dtype=jnp.uint32)
    s, *carried = jax.lax.sort((word,) + payload, num_keys=1, is_stable=False)
    return ((s & jnp.uint32((1 << bits) - 1)).astype(jnp.int32), *carried)


def _idx_dtype(cap: int):
    """Row-index dtype wide enough for ``cap`` rows: positions at or past
    2^31 wrap negative in int32, so the >31-bit regime (reachable
    internally, e.g. count_leq_dense's merged csum + out_capacity array)
    carries indices in int64 (round-4 advice finding 1)."""
    return jnp.int64 if cap > (1 << 31) - 1 else jnp.int32


@stage("compact.partition")
def compact_indices(mask: jax.Array, *payload: jax.Array):
    """(idx, new_count, *carried): the first ``new_count`` entries of
    ``idx`` are the row indices where ``mask`` is True, in order; entries
    past new_count are in-bounds filler that callers must mask.  new_count
    is a scalar (int32 below 2^31 rows, int64 past it).  ``carried`` is
    each 1-D ``payload`` array as ``jnp.take(x, idx)`` would return it,
    filler and all (the module docstring says how)."""
    cap = mask.shape[0]
    it = _idx_dtype(cap)
    new_count = jnp.sum(mask, dtype=it)
    if permute_mode() == "sort":
        idx, carried = _mask_sort_perm(mask, payload)
        return (idx, new_count, *carried)
    iota = jnp.arange(cap, dtype=it)
    pos = jnp.cumsum(mask, dtype=it) - 1
    idx = jnp.zeros((cap,), it).at[
        jnp.where(mask, pos, cap)].set(iota, mode="drop")
    return (idx, new_count, *(jnp.take(x, idx) for x in payload))


@stage("compact.partition")
def partition_indices(mask: jax.Array, *payload: jax.Array):
    """(perm, true_count, *carried): a full stable partition permutation —
    mask-True row indices first (in order), then every mask-False index (in
    order).  Unlike ``compact_indices`` the tail is the real False rows, so
    ``perm`` is a permutation of [0, n) usable wherever each row must appear
    exactly once (e.g. reordering a table without dropping rows).
    ``carried`` is each ``payload`` array as ``jnp.take(x, perm)``."""
    cap = mask.shape[0]
    it = _idx_dtype(cap)
    nt = jnp.sum(mask, dtype=it)
    if permute_mode() == "sort":
        perm, carried = _mask_sort_perm(mask, payload)
        return (perm, nt, *carried)
    iota = jnp.arange(cap, dtype=it)
    ct = jnp.cumsum(mask, dtype=it)
    cf = iota + 1 - ct  # cumsum of ~mask without a second scan
    dest = jnp.where(mask, ct - 1, nt + cf - 1)
    perm = jnp.zeros((cap,), it).at[dest].set(iota)
    return (perm, nt, *(jnp.take(x, perm) for x in payload))


def count_leq_dense(sorted_vals: jax.Array, num_queries: int) -> jax.Array:
    """``out[k] = #{i : sorted_vals[i] <= k}`` for k in [0, num_queries) —
    ``searchsorted(sorted_vals, arange(num_queries), side='right')`` for a
    monotone int array — via one merged u32 sort plus one packed
    compaction (both bandwidth-bound on TPU, unlike a scatter/histogram).

    Packing: word = value << 1 | tag (tag 1 = query).  A value v sorts
    before query k exactly when v <= k, and queries keep their ascending
    order, so query k's merged position p satisfies p = #{v <= k} + k.
    Values are clipped to num_queries (entries beyond every query count
    toward no query, preserving searchsorted semantics for the dense
    query range)."""
    vals = jnp.clip(sorted_vals, 0, num_queries).astype(jnp.uint32) << 1
    queries = (jnp.arange(num_queries, dtype=jnp.uint32) << 1) | 1
    merged = jax.lax.sort(jnp.concatenate([vals, queries]), is_stable=False)
    p, _ = compact_indices((merged & 1) == 1)
    return p[:num_queries] - jnp.arange(num_queries, dtype=jnp.int32)


@stage("compact.permute")
def inverse_permute(perm: jax.Array, *fields: jax.Array) -> Tuple[jax.Array, ...]:
    """``out[perm[i]] = fields[..][i]`` for each field — the inverse-
    permutation apply (``perm`` must be a permutation of [0, n)).

    scatter mode: one scatter per field.  sort mode: ONE multi-operand
    ``lax.sort`` keyed on ``perm`` (unique keys, unstable OK) carries all
    fields to their destinations in a single fused pass."""
    if permute_mode() == "sort":
        sorted_ops = jax.lax.sort((perm.astype(jnp.uint32),) + tuple(fields),
                                  num_keys=1, is_stable=False)
        return tuple(sorted_ops[1:])
    return tuple(jnp.zeros_like(f).at[perm].set(
        f, unique_indices=True, mode="promise_in_bounds") for f in fields)


def live_mask(capacity: int, row_count) -> jax.Array:
    """bool[capacity]: True for rows below the dynamic row count."""
    return jnp.arange(capacity, dtype=jnp.int32) < row_count
