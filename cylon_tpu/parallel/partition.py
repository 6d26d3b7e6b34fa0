"""Row -> target-shard assignment (the "sharding strategies").

TPU-native replacement for the reference's partition layer
(cpp/src/cylon/partition/partition.cpp, arrow/arrow_partition_kernels.hpp):

- ``hash_targets``: multi-column murmur-style row hash, modulo (or mask for
  power-of-two world sizes, arrow_partition_kernels.hpp:60-70) — the analog
  of PartitionByHashing + ModuloPartitionKernel/NumericHashPartitionKernel.
- ``range_targets``: the sampled-histogram range partitioner behind
  DistributedSort (arrow_partition_kernels.hpp:394-519 RangePartitionKernel):
  sample rows, AllReduce global min/max, build a global histogram with one
  psum (the mirror of the MPI_Allreduce at :469-480), prefix-sum it into
  monotone bin->partition cut points.

Both run *inside* shard_map: each shard computes targets for its own rows.
Padding rows get target ``world`` (a sentinel bucket nothing is sent to).

``column_stats`` rides the same pre-pass (the count-matrix program that
already touches every key): it observes each column's realized value
range / string extent / cardinality and reduces them to REPLICATED
scalars with allreduce collectives, so every process derives the same
compression spec (``plane.build_spec``) for the exchange that follows.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import precision
from ..column import Column
from ..ops import compact as compact_mod
from ..ops import hashing
from ..ops import pallas_kernels
from . import collectives
from . import plane as plane_mod


def hash_targets(cols: Sequence[Column], count, key_idx: Sequence[int],
                 world: int) -> jax.Array:
    """int32[cap] target shard per row (``world`` for padding rows).

    On TPU, fixed-width keys route through the fused Pallas murmur3 kernel
    (ops/pallas_kernels.hash_partition — bit-identical to the native host
    hasher, so host- and device-partitioned rows agree); string keys and
    CPU execution use the vectorized jnp hash."""
    cap = cols[0].data.shape[0]
    _, t = key_hashes(cols, key_idx, world)
    live = compact_mod.live_mask(cap, count)
    return jnp.where(live, t, jnp.int32(world))


def key_hashes(cols: Sequence[Column], key_idx: Sequence[int],
               world: int) -> Tuple[jax.Array, jax.Array]:
    """(uint32 hash, int32 hash target) of every row's key, padding rows
    included: the hash ``hash_targets`` places rows by, so that a row's
    key hash found here names the shard that row is sent to."""
    key_cols = [cols[i] for i in key_idx]
    if precision.on_tpu() and pallas_kernels.supported(key_cols):
        return pallas_kernels.hash_partition(key_cols, world)
    h = hashing.hash_columns(key_cols)
    if world & (world - 1) == 0:
        t = (h & jnp.uint32(world - 1)).astype(jnp.int32)
    else:
        t = (h % jnp.uint32(world)).astype(jnp.int32)
    return h, t


def range_targets(col: Column, count, world: int, *, num_bins: int,
                  num_samples: int, ascending: bool = True,
                  nulls_first: bool = True) -> jax.Array:
    """Range-partition targets for one sort column, globally monotone:
    rows in shard t all order before rows in shard t+1.

    Strings go BEYOND the reference (its RangePartitionKernel is numeric
    only, arrow_partition_kernels.hpp:394-519): the leading 4 bytes pack
    big-endian into a uint32 whose numeric order equals bytewise
    lexicographic order, so the bin map stays monotone w.r.t. the true key
    order — prefix collisions can only merge bins (worse balance), never
    reorder them, and the post-shuffle local sort uses the full key.

    Collective footprint (identical in shape to the reference): pmin/pmax of
    the column extrema + one psum of the (num_bins,) sample histogram.
    """
    cap = col.data.shape[0]
    live = compact_mod.live_mask(cap, count) & col.validity
    if col.is_string:
        from ..ops import keys as keys_mod

        # first word packs big-endian into the high bytes of a uint64;
        # keep the top 32 bits (4 leading characters) as the bin key
        word0 = keys_mod.pack_string_words(col.data[:, :4])[0]
        data = (word0 >> jnp.uint64(32)).astype(jnp.uint32)
    else:
        data = col.data
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int32)
    # bin math precision only shapes load balance, never correctness: the
    # value->bin map stays monotone under any float rounding
    facc = precision.float_acc()
    fdata = data.astype(facc)

    big = jnp.asarray(jnp.finfo(facc).max, facc)
    gmin = collectives.allreduce_min(jnp.min(jnp.where(live, fdata, big)))
    gmax = collectives.allreduce_max(jnp.max(jnp.where(live, fdata, -big)))
    span = jnp.maximum(gmax - gmin, jnp.asarray(jnp.finfo(facc).tiny, facc))

    # deterministic stride sample of live rows (reference samples `num_samples`
    # values per worker, partition.cpp:181)
    n_live = jnp.sum(live, dtype=jnp.int32)
    pos = (jnp.arange(num_samples, dtype=facc)
           * jnp.maximum(n_live, 1).astype(facc) / num_samples)
    pos = jnp.clip(pos.astype(jnp.int32), 0, cap - 1)
    # live rows are not contiguous post-filter; sample from a compacted view
    perm, m = compact_mod.compact_indices(live)
    sample_idx = jnp.take(perm, jnp.clip(pos, 0, cap - 1))
    sample = jnp.take(fdata, sample_idx)
    sample_ok = pos < m

    sbin = jnp.clip(((sample - gmin) / span * num_bins).astype(jnp.int32),
                    0, num_bins - 1)
    if compact_mod.permute_mode() == "sort":
        # histogram as prefix-count differences (merged-sort searchsorted
        # — count_leq_dense takes any input order); dead samples park in
        # a clip-guaranteed in-range bin and are excluded by remapping
        # them past every query
        sbin_ok = jnp.where(sample_ok, sbin, num_bins)
        leq = compact_mod.count_leq_dense(sbin_ok, num_bins)
        hist = jnp.diff(leq, prepend=0).astype(jnp.int32)
    else:
        hist = jax.ops.segment_sum(sample_ok.astype(jnp.int32), sbin,
                                   num_bins)
    hist = collectives.allreduce_sum(hist)          # global histogram (psum)
    total = jnp.maximum(jnp.sum(hist), 1)

    # monotone bin -> partition map from the histogram mass midpoint
    cum = jnp.cumsum(hist)
    mid = cum.astype(facc) - hist.astype(facc) / 2
    bin_part = jnp.clip((mid * world / total).astype(jnp.int32), 0, world - 1)
    if not ascending:
        bin_part = (world - 1) - bin_part

    rbin = jnp.clip(((fdata - gmin) / span * num_bins).astype(jnp.int32),
                    0, num_bins - 1)
    t = jnp.take(bin_part, rbin)
    null_target = jnp.int32(0 if nulls_first else world - 1)
    t = jnp.where(col.validity, t, null_target)
    row_live = compact_mod.live_mask(cap, count)
    return jnp.where(row_live, t, jnp.int32(world))


# ---------------------------------------------------------------------------
# compression observation pass (PR 10)
# ---------------------------------------------------------------------------


def stats_arity(cols: Sequence[Column]) -> int:
    """How many replicated stat arrays column_stats returns — the host
    side sizes its out_specs / unpacking from the same layout walk."""
    lay = plane_mod.stats_layout(cols)
    return sum(2 if k == "int" else 3 if k == "str" else 0 for k in lay)


def column_stats(cols: Sequence[Column], count) -> Tuple[jax.Array, ...]:
    """Observed-value stats of every LIVE row, replicated across the mesh
    (runs inside shard_map; allreduce collectives make every shard — and
    every process — see identical values).  Flat tuple matching
    ``plane.stats_layout``: (min, max) per integer column; (nonzero byte
    extent, max length, max per-shard distinct count) per string column.

    Liveness is ``row < count``, NOT validity: null rows' raw payload
    bits travel through the exchange and must stay inside the observed
    range, while padding rows beyond the count are never sent and may
    fall outside it."""
    cap = cols[0].data.shape[0]
    live = compact_mod.live_mask(cap, count)
    out: List[jax.Array] = []
    for c, kind in zip(cols, plane_mod.stats_layout(cols)):
        if kind == "int":
            info = jnp.iinfo(c.data.dtype)
            big = jnp.asarray(info.max, c.data.dtype)
            small = jnp.asarray(info.min, c.data.dtype)
            mn = collectives.allreduce_min(
                jnp.min(jnp.where(live, c.data, big)))
            mx = collectives.allreduce_max(
                jnp.max(jnp.where(live, c.data, small)))
            out.append(jnp.reshape(mn, (1,)))
            out.append(jnp.reshape(mx, (1,)))
        elif kind == "str":
            w = c.string_width
            if w:
                nzcol = jnp.any((c.data != 0) & live[:, None], axis=0)
                extent = jnp.max(jnp.where(
                    nzcol, jnp.arange(1, w + 1, dtype=jnp.int32), 0))
            else:
                extent = jnp.int32(0)
            maxlen = jnp.max(jnp.where(live, c.lengths, 0))
            # distinct (bytes, length) count among live rows, over the
            # SAME key tuple the codec's local dictionary build walks
            # (plane.string_key_words — single-sourced, or lcap would
            # silently under-cover the dictionary); non-live rows
            # collapse into one sentinel group, so the observed count
            # stays a safe upper bound for the codec's dictionary
            # (padding rows are the zero row, present via its reserved
            # entry)
            sent = jnp.uint64(0xFFFFFFFFFFFFFFFF)
            kws = [jnp.where(live, wv, sent)
                   for wv in plane_mod.string_key_words(c)]
            _swv, flag = plane_mod.sorted_distinct_flags(kws)
            nun = jnp.sum(flag, dtype=jnp.int32)
            out.append(jnp.reshape(collectives.allreduce_max(extent), (1,)))
            out.append(jnp.reshape(collectives.allreduce_max(maxlen), (1,)))
            out.append(jnp.reshape(collectives.allreduce_max(nun), (1,)))
    return tuple(out)
