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
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .. import precision
from . import compact


def segment_spans(new_group: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-segment [start, end) positions from a group-boundary mask.

    ``new_group[i]`` is True where sorted row i starts a new segment
    (position 0 must be True for any nonempty input).  Returns
    (start[cap], end[cap]) where segment g spans rows [start[g], end[g]);
    ids >= the number of segments get empty spans at cap.
    """
    cap = new_group.shape[0]
    starts_perm, num = compact.compact_indices(new_group)
    iota = jnp.arange(cap, dtype=jnp.int32)
    start = jnp.where(iota < num, starts_perm, cap)
    end = jnp.concatenate([start[1:], jnp.full((1,), cap, jnp.int32)])
    return start, end


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

    On TPU the three scans ride the two-sweep Pallas kernel
    (ops/pallas_scan.scan_1d); CYLON_TPU_SCAN=pallas/xla forces either
    path — see _pallas_plain_scan_selected for why."""
    n = member.shape[0]
    if _pallas_plain_scan_selected():
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


_SCAN_MODE: "str | None" = None  # None = read CYLON_TPU_SCAN


def set_scan(mode: "str | None") -> None:
    """Force ``"pallas"`` or ``"xla"`` plain scans in run_extents (None =
    env).  Clears jit caches like set_segsum — the knob is read at trace
    time inside jitted pipelines, so an env flip alone would silently
    keep the cached path and poison any in-process A/B."""
    global _SCAN_MODE
    if mode not in (None, "pallas", "xla"):
        raise ValueError(f"scan mode must be pallas/xla, got {mode}")
    if mode != _SCAN_MODE:
        jax.clear_caches()
    _SCAN_MODE = mode


def plain_scan_mode() -> str:
    """The plain-scan path trace-time state selects: ``"pallas"`` |
    ``"xla"`` (public accessor — bench reporting keys on it, like
    effective_mode for segsum)."""
    return "pallas" if _pallas_plain_scan_selected() else "xla"


def _pallas_plain_scan_selected() -> bool:
    """Whether run_extents' cumsum/cummax/cummin ride the Pallas scan.
    CYLON_TPU_SCAN=pallas/xla (or set_scan) forces it; unset picks Pallas
    on TPU, where XLA's reduce-window scans cost 18-45 s of compile EACH
    at 2^20 rows on a v5e (PERF.md, PR 22) against under a second for
    the kernel.  Read at trace time."""
    if _SCAN_MODE is not None:
        return _SCAN_MODE == "pallas"
    from .. import config

    mode = config.knob("CYLON_TPU_SCAN")
    if mode in ("pallas", "xla"):
        return mode == "pallas"
    return precision.on_tpu()


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


_SEGSUM_MODE: "str | None" = None  # None = read CYLON_TPU_SEGSUM


def set_segsum(mode: "str | None") -> None:
    """Force ``"prefix"``, ``"pallas"`` or ``"scatter"`` segment reductions
    (None = env).  ``pallas`` is prefix semantics through the two-sweep
    Pallas kernel (ops/pallas_scan.py) instead of lax.associative_scan.
    Clears jit caches like precision.set_accumulation — the knob is read
    at trace time, so cached kernels would otherwise keep the old path."""
    global _SEGSUM_MODE
    if mode not in (None, "prefix", "pallas", "scatter"):
        raise ValueError(
            f"segsum mode must be prefix/pallas/scatter, got {mode}")
    if mode != _SEGSUM_MODE:
        jax.clear_caches()
    _SEGSUM_MODE = mode


def effective_mode() -> str:
    """The segment-reduction path narrow-mode float/min/max reductions
    take: ``"pallas"`` | ``"prefix"`` | ``"scatter"``.  CYLON_TPU_SEGSUM
    (or set_segsum) forces one; unset is backend-aware like
    compact.permute_mode — scatter off the TPU (XLA:CPU scatter-adds are
    cheap and its associative_scan is not), a segmented scan on it
    (round-4 hardware: XLA:TPU serializes scatters), realized by the
    two-sweep Pallas kernel rather than ``prefix``'s
    lax.associative_scan: the chip's compiler takes 72 s for the latter
    at 2^20 rows and over 400 s at 2^22, about a second for the kernel
    at any size, and the two agree on the chip (PERF.md, PR 22).  Which
    is faster to RUN is not measured.  The 64-bit carve-outs in
    groupby._segment_aggregate are mode-independent: integer sums and
    wide accumulators keep the scatter in every mode (64-bit prefix
    fusions have crashed this TPU backend).  Read at trace time: set it
    before the first jitted compute or use set_segsum, which clears the
    jit caches."""
    if _SEGSUM_MODE is not None:
        return _SEGSUM_MODE
    from .. import config

    mode = config.knob("CYLON_TPU_SEGSUM")
    if mode in ("prefix", "pallas", "scatter"):
        return mode
    return "pallas" if precision.on_tpu() else "scatter"


def prefix_reductions_enabled() -> bool:
    """Whether segment reductions use a segmented scan (either
    realization) instead of scatter-adds."""
    return effective_mode() != "scatter"


def _pallas_scan_selected() -> bool:
    """Whether the Pallas kernel, not lax.associative_scan, backs
    segmented_reduce_sorted."""
    return effective_mode() == "pallas"


def segmented_reduce_sorted(x: jax.Array, new_group: jax.Array,
                            end: jax.Array, op: str) -> jax.Array:
    """Per-segment reduction over rows already grouped into runs, with NO
    scatter: a segmented ``lax.associative_scan`` over (value, reset-flag)
    pairs carries each run's running reduction — the combine restarts at
    run boundaries, so rounding stays per-segment exactly like the
    scatter-add it replaces — and the per-run total is gathered at the
    run's last row.  ``x`` must already be masked (null/padding rows set
    to the op's neutral element).  ``op``: 'sum' | 'min' | 'max'.

    Returns values indexed by segment id (same contract as
    ``jax.ops.segment_*`` with ``num_segments = len(x)``); ids past the
    number of segments read the clipped last row (callers mask by group
    liveness, as they already do for the scatter path)."""
    if _pallas_scan_selected() and x.dtype.itemsize == 4:
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
