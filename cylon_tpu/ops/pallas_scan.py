"""Pallas TPU segmented-scan kernel — the two-sweep replacement for
``lax.associative_scan`` in segment reductions.

Why: round-4 hardware settled that XLA:TPU serializes scatters, so
segment reductions ride a segmented ``lax.associative_scan``
(segments.segmented_reduce_sorted).  But XLA lowers an associative scan
as ~log2(n) materialized full-array passes over (value, flag) pairs —
hundreds of bytes of HBM traffic per element at 2^26 rows.  This kernel
does the same inclusive segmented scan in TWO bandwidth-bound sweeps
(~24 B/element total):

1. View the n elements as a (128, m) array: sublane s owns the
   contiguous range [s*m, (s+1)*m).  Sweep 1 runs one grid along the
   lane axis; each (128, bm) block computes an in-block Hillis-Steele
   segmented scan (log2(bm) vectorized roll+combine steps, VMEM
   resident) and stitches blocks with a per-sublane carry held in VMEM
   scratch — TPU grids execute sequentially, so the carry flows left to
   right across the whole sweep.  The per-sublane totals and
   reset-presence flags come out as a tiny (128, 1) side output.
2. The host combines those 128 pairs with one (cheap) exclusive
   segmented scan — carry_in[s] = running value entering sublane s.
3. Sweep 2 folds carry_in into every element positioned before its
   sublane's first segment boundary (the inclusive cum-OR of reset
   flags, recomputed in-block the same way).

The combine matches segments.segmented_reduce_sorted:
``(va, fa) o (vb, fb) = (fb ? vb : fn(va, vb), fa | fb)``.  Like the
associative scan it replaces, float sums round in combine-tree order —
contained per segment, but not bit-identical to a sequential sum (and
the two implementations' trees differ, so float results agree to
tolerance, not bitwise; int and min/max are exact).

Reference counterpart: the aggregation kernels this feeds replace
cpp/src/cylon/groupby/hash_groupby.cpp's per-row hash-map updates
(SURVEY §3.2); the kernel itself has no reference twin — it exists
because the TPU memory model punishes both hash maps and scatters.

The kernel runs natively on TPU; elsewhere ``pallas_call`` uses
interpret mode (tests), where ``jnp.roll`` stands in for
``pltpu.roll``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 128       # rows of the scan view; one contiguous range each
_BLOCK_LANES = 1024   # lanes per grid block (128*1024*4B = 512 KB/ref)

_FNS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _neutral(dtype, op: str):
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


def _roll_right(v: jax.Array, d: int, interpret: bool) -> jax.Array:
    """Shift lanes right by d along axis 1 (circular; callers mask the
    wrap).  pltpu.roll is the Mosaic-native rotate; interpret mode has no
    lowering for it, so tests take jnp.roll."""
    if interpret:
        return jnp.roll(v, d, axis=1)
    # typed shift: under jax_enable_x64 a bare Python int traces as i64
    # and Mosaic's dynamic_rotate takes an i32 amount
    return pltpu.roll(v, jnp.int32(d), axis=1)


def _block_specs(bm: int):
    """(128, bm) data block walking the lane axis, and the (128, 1) side
    column.  The zeros are typed: under jax_enable_x64 a bare Python 0
    traces as i64 and Mosaic rejects the mixed index-map signature."""
    return (pl.BlockSpec((_SUBLANES, bm), lambda i: (jnp.int32(0), i)),
            pl.BlockSpec((_SUBLANES, 1),
                         lambda i: (jnp.int32(0), jnp.int32(0))))


def _block_segscan(v: jax.Array, f: jax.Array, op: str, bm: int,
                   interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Inclusive segmented Hillis-Steele scan along the lane axis of one
    (128, bm) block.  f is uint32 0/1 reset flags; returns (values,
    inclusive cum-OR of f)."""
    fn = _FNS[op]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    d = 1
    while d < bm:
        vs = _roll_right(v, d, interpret)
        fs = _roll_right(f, d, interpret)
        live = lane >= d
        # combine (vs, fs) o (v, f): restart at boundaries, OR the flags
        v = jnp.where(live & (f == 0), fn(vs, v), v)
        f = jnp.where(live, f | fs, f)
        d *= 2
    return v, f


def _sweep1_kernel(op: str, bm: int, interpret: bool, x_ref, r_ref, out_ref,
                   tot_ref, any_ref, carry, or_acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry[:] = jnp.full(carry.shape, _neutral(carry.dtype, op))
        or_acc[:] = jnp.zeros(or_acc.shape, jnp.uint32)

    v, f = _block_segscan(x_ref[:], r_ref[:], op, bm, interpret)
    # fold the running carry into lanes before the block's first reset
    v = jnp.where(f == 0, _FNS[op](carry[:], v), v)
    out_ref[:] = v
    carry[:] = v[:, -1:]
    or_acc[:] = or_acc[:] | f[:, -1:]
    tot_ref[:] = carry[:]
    any_ref[:] = or_acc[:]


def _block_orscan(f: jax.Array, bm: int, interpret: bool) -> jax.Array:
    """Inclusive cum-OR along the lane axis — the flags-only half of
    _block_segscan (sweep 2 needs just the mask, not the values)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, f.shape, 1)
    d = 1
    while d < bm:
        fs = _roll_right(f, d, interpret)
        f = jnp.where(lane >= d, f | fs, f)
        d *= 2
    return f


def _sweep2_kernel(op: str, bm: int, interpret: bool, x_ref, r_ref, cin_ref,
                   out_ref, or_acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        or_acc[:] = jnp.zeros(or_acc.shape, jnp.uint32)

    f = _block_orscan(r_ref[:], bm, interpret)
    seen = or_acc[:] | f  # any reset in this sublane up to and incl. here
    out_ref[:] = jnp.where(seen == 0, _FNS[op](cin_ref[:], x_ref[:]),
                           x_ref[:])
    or_acc[:] = or_acc[:] | f[:, -1:]


@functools.partial(jax.jit,
                   static_argnames=("op", "bm", "interpret"))
def _segmented_scan_padded(x2: jax.Array, r2: jax.Array, op: str, bm: int,
                           interpret: bool) -> jax.Array:
    """x2, r2: (128, m) with m a multiple of bm."""
    m = x2.shape[1]
    grid = (m // bm,)
    blk, col = _block_specs(bm)
    partial_scan, totals, anyreset = pl.pallas_call(
        functools.partial(_sweep1_kernel, op, bm, interpret),
        grid=grid,
        in_specs=[blk, blk],
        out_specs=(blk, col, col),
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct((_SUBLANES, 1), x2.dtype),
                   jax.ShapeDtypeStruct((_SUBLANES, 1), jnp.uint32)),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, 1), x2.dtype),
                        pltpu.VMEM((_SUBLANES, 1), jnp.uint32)],
        interpret=interpret,
    )(x2, r2)

    # host stitch: exclusive segmented scan over the 128 sublane pairs
    fn = _FNS[op]

    def combine(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, fn(va, vb)), fa | fb

    tv, tf = jax.lax.associative_scan(
        combine, (totals[:, 0], anyreset[:, 0] != 0))
    neutral = _neutral(x2.dtype, op)
    carry_in = jnp.concatenate([jnp.full((1,), neutral, x2.dtype), tv[:-1]])
    carry_in = carry_in[:, None]

    return pl.pallas_call(
        functools.partial(_sweep2_kernel, op, bm, interpret),
        grid=grid,
        in_specs=[blk, blk, col],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, 1), jnp.uint32)],
        interpret=interpret,
    )(partial_scan, r2, carry_in)


def _block_scan_plain(v: jax.Array, op: str, bm: int,
                      interpret: bool) -> jax.Array:
    """Inclusive (unsegmented) Hillis-Steele scan along the lane axis —
    the flags-free fast path for cumsum/cummax/cummin."""
    fn = _FNS[op]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    d = 1
    while d < bm:
        vs = _roll_right(v, d, interpret)
        v = jnp.where(lane >= d, fn(vs, v), v)
        d *= 2
    return v


def _sweep1_plain_kernel(op: str, bm: int, interpret: bool, x_ref, out_ref,
                         tot_ref, carry):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry[:] = jnp.full(carry.shape, _neutral(carry.dtype, op))

    v = _FNS[op](carry[:], _block_scan_plain(x_ref[:], op, bm, interpret))
    out_ref[:] = v
    carry[:] = v[:, -1:]
    tot_ref[:] = carry[:]


@functools.partial(jax.jit, static_argnames=("op", "bm", "interpret"))
def _scan_padded(x2: jax.Array, op: str, bm: int, interpret: bool):
    m = x2.shape[1]
    grid = (m // bm,)
    blk, col = _block_specs(bm)
    partial_scan, totals = pl.pallas_call(
        functools.partial(_sweep1_plain_kernel, op, bm, interpret),
        grid=grid,
        in_specs=[blk],
        out_specs=(blk, col),
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct((_SUBLANES, 1), x2.dtype)),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, 1), x2.dtype)],
        interpret=interpret,
    )(x2)
    # sweep 2 degenerates to one fused broadcast: carry_in[s] combines
    # into every element of sublane s (no segment boundaries to respect)
    fn = _FNS[op]
    tv = jax.lax.associative_scan(fn, totals[:, 0])
    neutral = _neutral(x2.dtype, op)
    carry_in = jnp.concatenate([jnp.full((1,), neutral, x2.dtype), tv[:-1]])
    return fn(carry_in[:, None], partial_scan)


def _layout_1d(x: jax.Array, op: str, interpret: "bool | None",
               block_lanes: "int | None"):
    """Shared entry layout: validate, resolve interpret, pad ``x`` with
    the op's neutral to a whole (128, m) grid of bm-lane blocks.
    Returns (x2, bm, interpret) — single-sourced so scan_1d and
    segmented_scan can never disagree on the view."""
    if x.ndim != 1 or x.dtype.itemsize != 4:
        raise ValueError("pallas scan: 1-D 32-bit input required")
    if interpret is None:
        from .. import precision
        interpret = not precision.on_tpu()
    bm = block_lanes or _BLOCK_LANES
    n = x.shape[0]
    m = -(-n // _SUBLANES)
    m = -(-m // bm) * bm
    pad = _SUBLANES * m - n
    neutral = _neutral(x.dtype, op)
    xp = jnp.concatenate([x, jnp.full((pad,), neutral, x.dtype)]) if pad else x
    return xp.reshape(_SUBLANES, m), bm, interpret, pad


def scan_1d(x: jax.Array, op: str, reverse: bool = False,
            interpret: bool | None = None,
            block_lanes: int | None = None) -> jax.Array:
    """Inclusive scan of 1-D 32-bit ``x`` (cumsum/cummax/cummin family) —
    the Pallas sweep plus one broadcast combine instead of the ~log2(n)
    passes XLA materializes for lax.cumsum/cummax/cummin on this
    backend.  ``reverse=True`` scans right-to-left (the cummin
    run_extents needs) via flips that XLA fuses into the pad/reshape."""
    n = x.shape[0]
    if n == 0:
        return x
    if reverse:
        x = jnp.flip(x)
    x2, bm, interpret, _pad = _layout_1d(x, op, interpret, block_lanes)
    out = _scan_padded(x2, op, bm, interpret).reshape(-1)[:n]
    return jnp.flip(out) if reverse else out


def segmented_scan(x: jax.Array, reset: jax.Array, op: str,
                   interpret: bool | None = None,
                   block_lanes: int | None = None) -> jax.Array:
    """Inclusive segmented scan of 1-D ``x`` (32-bit dtype) with boolean
    ``reset`` marking segment starts; drop-in for the
    ``lax.associative_scan`` inside segments.segmented_reduce_sorted.
    Padding appended by the layout (to 128*bm granularity) is neutral
    with no resets, so it never perturbs real prefixes."""
    n = x.shape[0]
    if n == 0:
        return x
    x2, bm, interpret, pad = _layout_1d(x, op, interpret, block_lanes)
    rp = reset.astype(jnp.uint32)
    if pad:
        rp = jnp.concatenate([rp, jnp.zeros((pad,), jnp.uint32)])
    out2 = _segmented_scan_padded(x2, rp.reshape(_SUBLANES, x2.shape[1]),
                                  op, bm, interpret)
    return out2.reshape(-1)[:n]
