"""Mask -> front-packed compaction.

The reference materializes filtered results via ``arrow::compute::Filter``
over boolean masks (e.g. groupby index columns, hash_groupby.cpp:135-192;
Select, table.cpp:491-520).  The static-shape XLA equivalent: a stable sort
on the inverted mask yields a permutation that packs kept rows to the front
in original order; the new dynamic row count is the mask popcount.  One fused
sort+gather instead of a dynamically-sized filter.

Two interchangeable realizations, selected by :func:`permute_mode`:

- ``scatter``: cumsum destinations + one permuting scatter (one linear
  pass — optimal where scatter is cheap, e.g. XLA:CPU).
- ``sort``: pack (mask bit above row index) into ONE u32 word and
  ``lax.sort`` it — on TPU a full 64M-word sort measures ~4x FASTER than
  a same-size scatter (round-4 hardware profile: 213 ms sort vs ~900 ms
  per scatter pass at 2^26 rows/side), so sort-realized permutations are
  the TPU default.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .. import config, precision
from ..obs import stage


def permute_mode() -> str:
    """How permutations/compactions are materialized: "scatter" | "sort".

    CYLON_TPU_PERMUTE overrides; "auto" (default) picks "sort" on
    TPU-family backends (where XLA's sort is bandwidth-bound but its
    scatter serializes) and "scatter" elsewhere.  Read at trace time."""
    mode = config.knob("CYLON_TPU_PERMUTE")
    if mode in ("scatter", "sort"):
        return mode
    return "sort" if precision.on_tpu() else "scatter"


def index_bits(cap: int) -> int:
    """Bits needed to carry a row index in [0, cap) inside a packed sort
    word (shared with keys.lexsort_indices — the packing-width formula
    must stay single-sourced)."""
    return max(1, (cap - 1).bit_length()) if cap > 1 else 1


def _mask_sort_perm(mask: jax.Array) -> jax.Array:
    """Stable partition permutation via ONE single-word unstable sort:
    ``(~mask) << idx_bits | row`` — all words unique, ascending row bits
    make the unstable sort stable per mask value.  Arrays longer than
    2^31 rows can arise internally (e.g. the join expansion's merge of
    csum + out_capacity slots), where flag+index no longer fit u32; those
    fall back to a two-operand stable sort."""
    cap = mask.shape[0]
    bits = index_bits(cap)
    if bits + 1 > 32:
        # >=2^31 rows: int32 positions would wrap negative — exactly the
        # case this branch exists for — so carry the permutation in int64
        # (x64 is enabled package-wide; round-4 advice finding 1)
        iota = jnp.arange(cap, dtype=jnp.int64)
        _, perm = jax.lax.sort(
            (jnp.where(mask, jnp.uint32(0), jnp.uint32(1)), iota),
            num_keys=1, is_stable=True)
        return perm
    iota = jnp.arange(cap, dtype=jnp.uint32)
    word = (jnp.where(mask, jnp.uint32(0), jnp.uint32(1))
            << jnp.uint32(bits)) | iota
    s = jax.lax.sort(word, is_stable=False)
    return (s & jnp.uint32((1 << bits) - 1)).astype(jnp.int32)


def _idx_dtype(cap: int):
    """Row-index dtype wide enough for ``cap`` rows: positions at or past
    2^31 wrap negative in int32, so the >31-bit regime (reachable
    internally, e.g. count_leq_dense's merged csum + out_capacity array)
    carries indices in int64 (round-4 advice finding 1)."""
    return jnp.int64 if cap > (1 << 31) - 1 else jnp.int32


@stage("compact.partition")
def compact_indices(mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(idx, new_count): the first ``new_count`` entries of ``idx`` are the
    row indices where ``mask`` is True, in order; entries past new_count
    are in-bounds filler that callers must mask.  new_count is a scalar
    (int32 below 2^31 rows, int64 past it)."""
    cap = mask.shape[0]
    it = _idx_dtype(cap)
    new_count = jnp.sum(mask, dtype=it)
    if permute_mode() == "sort":
        return _mask_sort_perm(mask), new_count
    iota = jnp.arange(cap, dtype=it)
    pos = jnp.cumsum(mask, dtype=it) - 1
    idx = jnp.zeros((cap,), it).at[
        jnp.where(mask, pos, cap)].set(iota, mode="drop")
    return idx, new_count


@stage("compact.partition")
def partition_indices(mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(perm, true_count): a full stable partition permutation — mask-True
    row indices first (in order), then every mask-False index (in order).
    Unlike ``compact_indices`` the tail is the real False rows, so ``perm``
    is a permutation of [0, n) usable wherever each row must appear exactly
    once (e.g. reordering a table without dropping rows)."""
    cap = mask.shape[0]
    it = _idx_dtype(cap)
    nt = jnp.sum(mask, dtype=it)
    if permute_mode() == "sort":
        return _mask_sort_perm(mask), nt
    iota = jnp.arange(cap, dtype=it)
    ct = jnp.cumsum(mask, dtype=it)
    cf = iota + 1 - ct  # cumsum of ~mask without a second scan
    dest = jnp.where(mask, ct - 1, nt + cf - 1)
    perm = jnp.zeros((cap,), it).at[dest].set(iota)
    return perm, nt


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


def invperm_mode() -> str:
    """Sub-realization of sort-mode ``inverse_permute``: ``"sort"``
    (default — one multi-operand sort carries every field) or
    ``"gather"`` (one 2-operand sort builds the inverse index once, then
    one bandwidth-linear ``take`` per field).  The trade: a k-field
    multi-operand sort moves (k+1) operands through every sort pass,
    while the gather realization pays the sort passes once on 8 B/row
    and k linear gathers — the crossover is a hardware question
    (microbench + profiler A/B arms; CYLON_TPU_INVPERM overrides).
    Only meaningful when permute_mode() == "sort"."""
    return config.knob("CYLON_TPU_INVPERM")


@stage("compact.permute")
def inverse_permute(perm: jax.Array, *fields: jax.Array) -> Tuple[jax.Array, ...]:
    """``out[perm[i]] = fields[..][i]`` for each field — the inverse-
    permutation apply (``perm`` must be a permutation of [0, n)).

    scatter mode: one scatter per field.  sort mode: ONE multi-operand
    ``lax.sort`` keyed on ``perm`` (unique keys, unstable OK) carries all
    fields to their destinations in a single fused pass — or, under
    ``invperm_mode() == "gather"``, one 2-operand sort computes
    ``inv = argsort(perm)`` and each field is one linear gather
    ``take(f, inv)`` (equivalent because out[j] = f[inv[j]])."""
    if permute_mode() == "sort":
        if invperm_mode() == "gather":
            cap = perm.shape[0]
            # index dtype must widen with cap like _mask_sort_perm's
            # fallback: an int32 iota (and a u32 key cast) silently wraps
            # for cap >= 2^31, scrambling the inverse permutation
            it = _idx_dtype(cap)
            iota = jnp.arange(cap, dtype=it)  # payload: no cast back
            key = (perm.astype(jnp.uint32) if it == jnp.int32
                   else perm.astype(jnp.int64))
            _, inv = jax.lax.sort((key, iota), num_keys=1, is_stable=False)
            # inv is an argsort of a permutation — provably in bounds and
            # unique; the default fill mode would add a clamp+select per
            # element inside the very A/B this realization exists to win
            return tuple(f.at[inv].get(mode="promise_in_bounds",
                                       unique_indices=True)
                         for f in fields)
        sorted_ops = jax.lax.sort((perm.astype(jnp.uint32),) + tuple(fields),
                                  num_keys=1, is_stable=False)
        return tuple(sorted_ops[1:])
    return tuple(jnp.zeros_like(f).at[perm].set(
        f, unique_indices=True, mode="promise_in_bounds") for f in fields)


def live_mask(capacity: int, row_count) -> jax.Array:
    """bool[capacity]: True for rows below the dynamic row count."""
    return jnp.arange(capacity, dtype=jnp.int32) < row_count
