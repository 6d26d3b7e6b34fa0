"""Local group-by: sort + segment reduce.

TPU-native replacement for the reference's hash group-by
(cpp/src/cylon/groupby/hash_groupby.cpp:86-295 — ska::bytell_hash_map row→
group-id assignment + per-group State streaming) and pipeline group-by
(groupby/pipeline_groupby.cpp:29-115 — boundary scan over a pre-sorted key
column).  A hash table is the wrong shape for a vector machine; instead:

1. lexsort rows by the key columns (one fused ``lax.sort``, which carries
   the value columns as payload operands into group order),
2. dense group ids via adjacent equality + prefix sum,
3. each aggregation is a masked ``jax.ops.segment_*`` keyed by group id.

The aggregation op set and their state decompositions mirror the reference's
KernelTraits (compute/aggregate_kernels.hpp:38-200: SUM/MIN/MAX/COUNT/MEAN
(sum,count)/VAR (sumsq,sum,count)/STDDEV/NUNIQUE), including the
partial/final split used by the distributed two-phase group-by
(groupby/groupby.cpp:23-73): ``partial_ops`` names the partial columns a
pre-aggregation emits and ``final_of_partial`` how they recombine.
"""
from __future__ import annotations

import enum
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import dtypes, precision
from ..column import Column
from ..obs import stage
from . import compact, keys, segments


class AggOp(enum.IntEnum):
    """reference: compute/aggregate_kernels.hpp AggregationOpId."""

    SUM = 0
    MIN = 1
    MAX = 2
    COUNT = 3
    MEAN = 4
    VAR = 5
    STDDEV = 6
    NUNIQUE = 7
    SUMSQ = 8  # internal: sum of squares partial for VAR/STDDEV two-phase
    COUNTSUM = 9  # internal: sum of partial counts — i32 scatter in narrow

    @staticmethod
    def of(name: "str | AggOp") -> "AggOp":
        if isinstance(name, AggOp):
            return name
        m = {"sum": AggOp.SUM, "min": AggOp.MIN, "max": AggOp.MAX,
             "count": AggOp.COUNT, "mean": AggOp.MEAN, "avg": AggOp.MEAN,
             "var": AggOp.VAR, "std": AggOp.STDDEV, "stddev": AggOp.STDDEV,
             "nunique": AggOp.NUNIQUE}
        return m[name.lower()]


# -- two-phase decomposition (reference: groupby/groupby.cpp:47-62 runs
#    local partial agg, shuffles, then a final agg over partial columns) ----

def partial_ops(op: AggOp) -> Tuple[AggOp, ...]:
    """Partial aggregations whose columns must be shuffled for ``op``."""
    return {
        AggOp.SUM: (AggOp.SUM,),
        AggOp.MIN: (AggOp.MIN,),
        AggOp.MAX: (AggOp.MAX,),
        AggOp.COUNT: (AggOp.COUNT,),
        AggOp.MEAN: (AggOp.SUM, AggOp.COUNT),
        AggOp.VAR: (AggOp.SUM, AggOp.COUNT, AggOp.SUMSQ),
        AggOp.STDDEV: (AggOp.SUM, AggOp.COUNT, AggOp.SUMSQ),
        # the internal partial states are their own partials, so a caller
        # holding partial columns (the out-of-core cross-pass combine) can
        # push them through the distributed two-phase group-by unchanged
        AggOp.SUMSQ: (AggOp.SUMSQ,),
        AggOp.COUNTSUM: (AggOp.COUNTSUM,),
    }[op]


def combine_op(partial: AggOp) -> AggOp:
    """How a partial column recombines in the final phase."""
    if partial == AggOp.COUNT:
        # counts are bounded by rows, so the combine keeps the count
        # accumulator (i32 in narrow mode) instead of the int-SUM i64 path
        return AggOp.COUNTSUM
    if partial in (AggOp.SUM, AggOp.SUMSQ):
        return AggOp.SUM
    return partial  # MIN of mins, MAX of maxes


def _agg_out_dtype(op: AggOp, dt: dtypes.DataType):
    nar = precision.narrow()
    if op in (AggOp.COUNT, AggOp.NUNIQUE, AggOp.COUNTSUM):
        # declared int64 even in narrow mode: the device buffer stays i32
        # (cheap scatter) and widens at the host/arrow column boundary
        return dtypes.int64
    if op in (AggOp.MEAN, AggOp.VAR, AggOp.STDDEV, AggOp.SUMSQ):
        return dtypes.float_ if nar else dtypes.double
    if op == AggOp.SUM:
        if dtypes.is_floating(dt):
            if dt.type == dtypes.Type.DOUBLE and not nar:
                return dtypes.double
            return dtypes.float_
        return dtypes.int64
    return dt  # MIN/MAX keep the input type


def _segment_aggregate(op: AggOp, data, valid, gid, num_segments: int,
                       ddof: int, spans=None, boundaries=None):
    """One masked segment reduction; returns (values, validity_counts).

    Reductions are ``jax.ops.segment_*`` scatters with 32-bit operands
    wherever the semantics allow (counts accumulate i32 and widen after;
    f32 sums stay f32, matching the reference's KernelTraits accumulator of
    the input type) — 64-bit scatters profile ~8x slower on TPU, and the
    prefix-sum alternative (cumsum + boundary gather) SIGSEGVs/hangs this
    XLA TPU backend whenever several 64-bit prefix programs share one
    multi-aggregation fusion.  Only ops whose semantics require double
    accumulation (MEAN/VAR/STDDEV/SUMSQ, f64/int64 SUM) pay the 64-bit
    scatter.

    ``spans``: optional (start, end) per-segment row spans when rows are
    already ordered by ``gid`` (always true here — gids come from a sort or
    key-adjacent input).  In narrow mode, validity counts then use an exact
    i32 cumsum + boundary gather instead of a scatter (the cumsum peaks at
    the shard's physical row count, always an i32-safe quantity).  Value
    sums — including COUNTSUM, whose partial counts can represent far more
    rows than the shard holds — keep the per-segment scatter-add: a global
    prefix sum would overflow i32 for int data and lose precision for
    f32.

    ``boundaries`` (the run-start mask over the gid-sorted rows) opts the
    float/min/max reductions into the scatter-free segmented scan
    (segments.segmented_reduce_sorted) where the platform's segment
    reductions are scans (segments.effective_mode, on a TPU) —
    rounding stays per-segment because the scan's combine resets at run
    starts.  Integer sums stay on the scatter in every mode: their i64
    accumulator would make the scan a 64-bit prefix program (the class
    that has crashed this XLA TPU backend)."""
    sorted_counts = spans is not None and precision.narrow()
    use_scan = (sorted_counts and boundaries is not None
                and segments.effective_mode() == "pallas")
    if sorted_counts:
        start, end = spans
        cnt32 = segments.segment_sum_sorted(valid.astype(jnp.int32), start,
                                            end, jnp.int32)
    else:
        cnt32 = jax.ops.segment_sum(valid.astype(jnp.int32), gid, num_segments)
    cnt = cnt32 if precision.narrow() else cnt32.astype(jnp.int64)

    def fsum(x):
        if use_scan:
            return segments.segmented_reduce_sorted(x, boundaries, end, "sum")
        return jax.ops.segment_sum(x, gid, num_segments)

    if op == AggOp.COUNT:
        return cnt, cnt
    if op == AggOp.COUNTSUM:
        x = jnp.where(valid, data, 0).astype(precision.count_acc())
        s = jax.ops.segment_sum(x, gid, num_segments)
        return (s if precision.narrow() else s.astype(jnp.int64)), cnt
    if op == AggOp.SUMSQ:
        x = jnp.where(valid, data, 0).astype(precision.float_acc())
        return fsum(x * x), cnt
    if op == AggOp.SUM:
        acc = jnp.where(valid, data, jnp.zeros((), data.dtype))
        if jnp.issubdtype(data.dtype, jnp.floating):
            acc = acc.astype(precision.float_acc_for(data.dtype))
            return fsum(acc), cnt
        acc = acc.astype(precision.int_acc())
        return jax.ops.segment_sum(acc, gid, num_segments), cnt
    if op == AggOp.MIN or op == AggOp.MAX:
        if jnp.issubdtype(data.dtype, jnp.floating):
            sentinel = jnp.inf if op == AggOp.MIN else -jnp.inf
        elif data.dtype == jnp.bool_:
            data = data.astype(jnp.uint8)
            sentinel = 1 if op == AggOp.MIN else 0
        else:
            info = jnp.iinfo(data.dtype)
            sentinel = info.max if op == AggOp.MIN else info.min
        masked = jnp.where(valid, data, jnp.asarray(sentinel, data.dtype))
        if use_scan and masked.dtype.itemsize <= 4:
            out = segments.segmented_reduce_sorted(
                masked, boundaries, end, "min" if op == AggOp.MIN else "max")
        else:
            f = jax.ops.segment_min if op == AggOp.MIN else jax.ops.segment_max
            out = f(masked, gid, num_segments)
        return jnp.where(cnt > 0, out, jnp.zeros((), out.dtype)), cnt
    if op in (AggOp.MEAN, AggOp.VAR, AggOp.STDDEV):
        facc = precision.float_acc()
        x = jnp.where(valid, data, 0).astype(facc)
        s = fsum(x)
        if op == AggOp.MEAN:
            return s / jnp.maximum(cnt, 1).astype(facc), cnt
        s2 = fsum(x * x)
        n = jnp.maximum(cnt, 1).astype(facc)
        var = (s2 - s * s / n) / jnp.maximum(n - ddof, 1.0)
        var = jnp.maximum(var, 0.0)
        if op == AggOp.STDDEV:
            var = jnp.sqrt(var)
        return var, jnp.where(cnt - ddof > 0, cnt, 0)
    if op == AggOp.NUNIQUE:
        # distinct (gid, value) pairs: sort values within segments and count
        # adjacency breaks — handled in hash_groupby via a secondary sort.
        raise NotImplementedError("NUNIQUE is computed in hash_groupby")
    raise ValueError(op)


@partial(jax.jit, static_argnames=("key_idx", "aggs", "ddof"))
def hash_groupby(cols: Tuple[Column, ...], count,
                 key_idx: Tuple[int, ...],
                 aggs: Tuple[Tuple[int, AggOp], ...],
                 ddof: int = 0):
    """Group rows by ``key_idx`` columns and aggregate.

    Output columns: the key columns (one row per distinct live key, in key
    order) followed by one column per (value column, op) pair.  Returns
    (columns, group_count).
    """
    cap = cols[0].data.shape[0]
    key_cols = [cols[i] for i in key_idx]
    # the value and the key columns ride the sort into group order
    # (keys.pack_payload)
    values = {i: cols[i] for i, _ in aggs}
    with stage("groupby.gather"):
        buffers, columns = jax.tree.flatten(values)
        lanes, layout = keys.pack_payload(buffers + jax.tree.leaves(key_cols))
    with stage("groupby.sort"):
        operands = keys.build_operands(key_cols, count, cap)
        perm, sorted_ops, lanes = keys.lexsort_indices(operands, cap, lanes)
    with stage("groupby.gather"):
        values = jax.tree.unflatten(columns, [
            jnp.take(buffer, perm, axis=0, mode="clip") if rode is None
            else rode
            for buffer, rode in zip(buffers, keys.unpack_payload(
                lanes, layout[:len(buffers)]))])
    with stage("groupby.boundaries"):
        new_group = ~keys.rows_equal_adjacent(sorted_ops)
    out_cols, gid, start, end, live, num_groups, group_live = _groups(
        new_group, count, key_cols, lanes, layout[len(buffers):], perm)

    for col_idx, op in aggs:
        out_cols.append(_aggregate(op, values[col_idx], live, gid, cap, ddof,
                                   start, end, new_group, group_live,
                                   cols[col_idx].dtype))
    return tuple(out_cols), num_groups


def _groups(new_group, count, key_cols, lanes, layout, perm=None):
    """The groups of rows that lie in group order, from their run-start
    mask: ``(key columns, gid, start, end, live, num_groups, group_live)``.

    The key columns' buffers ride the compaction that finds the group
    starts (``segments.segment_spans``) to each group's first row:
    ``layout`` is ``keys.pack_payload``'s of ``jax.tree.leaves(key_cols)``
    and ``lanes`` its lanes in group order.  A buffer the layout marks
    ``None`` (a string's byte matrix, lanes past the budget) is taken
    through the source rows of the group starts: ``perm``, the rows' order,
    carried by the same compaction, or the starts themselves where the
    input lies in group order.  Each column reads as
    ``Column.take(..., valid_mask=group_live)`` does, bit for bit."""
    cap = new_group.shape[0]
    buffers, columns = jax.tree.flatten(key_cols)
    used = sorted({where[0] for where in layout if where is not None})
    src = [perm] if perm is not None and None in layout else []
    with stage("groupby.boundaries"):
        gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
        start, end, *carried = segments.segment_spans(
            new_group, *(lanes[lane] for lane in used), *src)
        iota = jnp.arange(cap, dtype=jnp.int32)
        # padding sorted last -> first `count` sorted rows live
        live = iota < count
        num_groups = jnp.where(
            count > 0, jnp.take(gid, jnp.clip(count - 1, 0, cap - 1)) + 1, 0)
        group_live = iota < num_groups
    with stage("groupby.keys"):
        leader_src = carried.pop() if src else jnp.clip(start, 0, cap - 1)
        rode = keys.unpack_payload(dict(zip(used, carried)), layout)
        key_cols = jax.tree.unflatten(columns, [
            jnp.take(buffer, leader_src, axis=0, mode="clip") if r is None
            else r for buffer, r in zip(buffers, rode)])
        return ([kc.masked(group_live) for kc in key_cols], gid, start, end,
                live, num_groups, group_live)


def _aggregate(op: AggOp, vcol: Column, live, gid, cap: int, ddof: int,
               start, end, new_group, group_live, in_dtype) -> Column:
    """One aggregate of one value column that lies in group order."""
    with stage("groupby.reduce"):
        vvalid = vcol.validity & live
        if op == AggOp.NUNIQUE:
            vals, cnts = _nunique(vcol, vvalid, gid, cap)
        else:
            if vcol.is_string:
                raise TypeError(f"aggregation {op.name} unsupported on strings")
            vals, cnts = _segment_aggregate(op, vcol.data, vvalid, gid,
                                            cap, ddof, spans=(start, end),
                                            boundaries=new_group)
        if op in (AggOp.COUNT, AggOp.COUNTSUM, AggOp.NUNIQUE):
            validity = group_live  # a count of zero values is a valid 0
        else:
            validity = group_live & (cnts > 0)
        vals = jnp.where(validity, vals, jnp.zeros((), vals.dtype))
    return Column(vals, validity, None, _agg_out_dtype(op, in_dtype))


def _nunique(vcol: Column, vvalid, gid, cap: int):
    """Distinct non-null values per group via a (gid, value) lexsort."""
    ops = [~vvalid, gid] + keys.column_operands(vcol, with_validity=False)
    perm, sorted_ops, _ = keys.lexsort_indices(ops, cap)
    eq = keys.rows_equal_adjacent(sorted_ops)
    # sorted_ops are packed words: recover fields through the permutation
    svalid = jnp.take(vvalid, perm)
    gsorted = jnp.take(gid, perm)
    new_distinct = (~eq) & svalid
    if compact.permute_mode() == "sort":
        # valid rows sort first (primary operand ~vvalid), so the valid
        # prefix is gid-ascending: per-gid distinct counts are prefix-sum
        # differences at merged-searchsorted group bounds — no scatter
        gclean = jnp.where(svalid, gsorted, cap)
        ub = compact.count_leq_dense(gclean, cap)
        p0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(new_distinct.astype(jnp.int32))])
        e = jnp.take(p0, ub)  # distinct count up to each group's end
        cnt = e - jnp.concatenate([jnp.zeros((1,), jnp.int32), e[:-1]])
    else:
        # i32 scatter-add, widened after: 64-bit scatters are ~8x slower
        cnt = jax.ops.segment_sum(new_distinct.astype(jnp.int32), gsorted,
                                  cap)
    return (cnt if precision.narrow() else cnt.astype(jnp.int64)), cnt


@partial(jax.jit, static_argnames=("key_idx", "aggs", "ddof"))
def pipeline_groupby(cols: Tuple[Column, ...], count,
                     key_idx: Tuple[int, ...],
                     aggs: Tuple[Tuple[int, AggOp], ...],
                     ddof: int = 0):
    """Group-by for key-sorted input (reference: pipeline_groupby.cpp): group
    boundaries come from adjacent comparison in row order — no sort."""
    cap = cols[0].data.shape[0]
    key_cols = [cols[i] for i in key_idx]
    with stage("groupby.boundaries"):
        operands = [keys.padding_operand(cap, count)]
        for kc in key_cols:
            operands.extend(keys.column_operands(kc))
        new_group = ~keys.rows_equal_adjacent(keys.pack_operands(operands))
        lanes, layout = keys.pack_payload(jax.tree.leaves(key_cols))
    out_cols, gid, start, end, live, num_groups, group_live = _groups(
        new_group, count, key_cols, lanes, layout)

    for col_idx, op in aggs:
        out_cols.append(_aggregate(op, cols[col_idx], live, gid, cap, ddof,
                                   start, end, new_group, group_live,
                                   cols[col_idx].dtype))
    return tuple(out_cols), num_groups
