"""Sorted-segment reductions without scatter.

``jax.ops.segment_*`` lowers to scatter-add, which XLA serializes on TPU —
profiled at ~0.8 s for a 4M-row float64/int64 scatter vs ~70 ms for a
float64 cumsum of the same length.  Every segment reduction in this
framework runs over rows *already sorted by group id* (group ids come from a
lexsort — ops/keys.dense_group_ids), so the TPU-native formulation is:

    sum over segment g  =  csum[end_g] - csum[start_g]

with segment spans recovered once per groupby from the group-boundary mask
via a cumsum-scatter compaction (ops/compact.compact_indices).  This is
the replacement for the reference's per-group accumulator State streaming
(cpp/src/cylon/groupby/hash_groupby.cpp:135-192 aggregate<op,T> and
compute/aggregate_kernels.hpp KernelTraits): the prefix sum *is* the
running state, evaluated for all groups at once.

MIN/MAX keep ``jax.ops.segment_min/max`` — their operands stay in the input
dtype (int32/float32 scatters profile ~8x faster than 64-bit ones) and have
no cancellation-safe prefix formulation.

Which scan and which segment reduction a platform gets: the table in
``realization.py``, read by ``plain_scan_mode`` and ``effective_mode``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .. import precision
from . import compact, realization


def segment_spans(new_group: jax.Array, *payload: jax.Array):
    """Per-segment [start, end) positions from a group-boundary mask.

    ``new_group[i]`` is True where sorted row i starts a new segment
    (position 0 must be True for any nonempty input).  Returns
    (start[cap], end[cap], *carried) where segment g spans rows [start[g],
    end[g]); ids >= the number of segments get empty spans at cap.
    ``carried`` is each 1-D ``payload`` array's row at every segment's
    start, moved by the compaction itself (``compact.compact_indices``);
    past the number of segments it holds filler.
    """
    cap = new_group.shape[0]
    starts_perm, num, *carried = compact.compact_indices(new_group, *payload)
    iota = jnp.arange(cap, dtype=jnp.int32)
    start = jnp.where(iota < num, starts_perm, cap)
    end = jnp.concatenate([start[1:], jnp.full((1,), cap, jnp.int32)])
    return (start, end, *carried)


def run_extents(member: jax.Array, new_group: jax.Array,
                is_run_end: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per sorted position: (# True ``member`` rows before this position's
    run, # True ``member`` rows inside the run).  ``new_group`` marks run
    starts and ``is_run_end`` run ends over the same sorted order.  One
    cumsum + one cummax run-start broadcast + one suffix-cummin run-end
    broadcast — no scatters (the per-gid histogram scatter-add this
    replaces serializes on TPU).

    Precondition (as for segment_spans): ``new_group[0]`` must be True for
    nonempty input — otherwise ``start`` stays -1 across the first run.
    All callers satisfy it because rows_equal_adjacent forces row 0 to
    start a run.

    On a TPU the three scans ride the two-sweep Pallas kernel
    (ops/pallas_scan.scan_1d)."""
    n = member.shape[0]
    if plain_scan_mode() == "pallas":
        from . import pallas_scan

        incl = pallas_scan.scan_1d(member.astype(jnp.int32), "sum")
        excl = incl - member.astype(jnp.int32)
        start = pallas_scan.scan_1d(
            jnp.where(new_group, excl, jnp.int32(-1)), "max")
        end = pallas_scan.scan_1d(
            jnp.where(is_run_end, incl, jnp.int32(n + 1)), "min",
            reverse=True)
        return start, end - start
    incl = jnp.cumsum(member.astype(jnp.int32))
    excl = incl - member.astype(jnp.int32)
    start = jax.lax.cummax(jnp.where(new_group, excl, jnp.int32(-1)))
    end = jax.lax.cummin(jnp.where(is_run_end, incl, jnp.int32(n + 1)),
                         reverse=True)
    return start, end - start


def plain_scan_mode() -> str:
    """How run_extents' cumsum/cummax/cummin run: ``"pallas"`` | ``"xla"``."""
    return realization.current().scan


def _span_take(csum0: jax.Array, pos: jax.Array) -> jax.Array:
    return jnp.take(csum0, pos, mode="clip")


def segment_sum_sorted(x: jax.Array, start: jax.Array, end: jax.Array,
                       acc_dtype=None) -> jax.Array:
    """Segment sums via prefix sum + boundary gather.  ``x`` must already be
    masked (padding/null rows zeroed).  ``acc_dtype`` defaults to the
    precision policy's accumulator (f64/i64 wide, f32/i64 narrow) — the
    prefix sum over the whole column needs the headroom even when
    per-segment sums are small."""
    if acc_dtype is None:
        if jnp.issubdtype(x.dtype, jnp.floating):
            acc_dtype = precision.float_acc()
        elif x.dtype == jnp.bool_:
            acc_dtype = jnp.int32
        else:
            acc_dtype = precision.int_acc()
    csum = jnp.cumsum(x.astype(acc_dtype))
    csum0 = jnp.concatenate([jnp.zeros((1,), acc_dtype), csum])
    return _span_take(csum0, end) - _span_take(csum0, start)


def segment_count_sorted(valid: jax.Array, start: jax.Array,
                         end: jax.Array) -> jax.Array:
    """Number of True rows per segment (int64, matching the reference's
    COUNT output type)."""
    return segment_sum_sorted(valid.astype(jnp.int32), start, end,
                              jnp.int32).astype(jnp.int64)


def effective_mode() -> str:
    """The path narrow-mode float/min/max segment reductions take:
    ``"pallas"`` (a segmented scan, segmented_reduce_sorted) |
    ``"scatter"`` (``jax.ops.segment_*``).  The 64-bit carve-outs in
    groupby._segment_aggregate hold on every platform: integer sums and
    wide accumulators keep the scatter (64-bit prefix fusions have crashed
    this TPU backend)."""
    return realization.current().segsum


def segmented_reduce_sorted(x: jax.Array, new_group: jax.Array,
                            end: jax.Array, op: str) -> jax.Array:
    """Per-segment reduction over rows already grouped into runs, with NO
    scatter: a segmented scan over (value, reset-flag) pairs carries each
    run's running reduction — the combine restarts at run boundaries, so
    rounding stays per-segment exactly like the scatter-add it replaces —
    and the per-run total is gathered at the run's last row.  Rows of four
    bytes go through the two-sweep Pallas kernel (ops/pallas_scan.py),
    every other width through ``lax.associative_scan``.  ``x`` must already
    be masked (null/padding rows set to the op's neutral element).  ``op``:
    'sum' | 'min' | 'max'.

    Returns values indexed by segment id (same contract as
    ``jax.ops.segment_*`` with ``num_segments = len(x)``); ids past the
    number of segments read the clipped last row (callers mask by group
    liveness, as they already do for the scatter path)."""
    if x.dtype.itemsize == 4:
        from . import pallas_scan

        run_val = pallas_scan.segmented_scan(x, new_group, op)
        return jnp.take(run_val, end - 1, mode="clip")

    fns = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
    fn = fns[op]

    def combine(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, fn(va, vb)), fa | fb

    run_val, _ = jax.lax.associative_scan(combine, (x, new_group))
    return jnp.take(run_val, end - 1, mode="clip")
