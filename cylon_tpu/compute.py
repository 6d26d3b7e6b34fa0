"""Element-wise compute over Tables: comparison / math / logical ops,
null handling, membership.

TPU-native analog of PyCylon's compute layer (reference:
python/pycylon/data/compute.pyx:29-587 — table↔scalar/array comparison ops,
math ops with division guards, is_null/invert/neg, is_in, drop_na,
unique/nunique) and the Table method surface that consumes it
(python/pycylon/data/table.pyx:1170-1598 dunders, 1599-2146
fillna/where/isnull/dropna/isin).

All ops are shard-local element-wise programs: applied directly to the
sharded global column buffers, XLA keeps the sharding and runs them on each
device's shard — no collective traffic.  Padding rows are kept zeroed so
downstream kernels' invariants hold.
"""
from __future__ import annotations

import operator
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes
from .column import Column
from .status import Code, CylonError

Scalar = Union[int, float, bool, str, np.generic]

_CMP_OPS = {
    "eq": operator.eq, "ne": operator.ne, "lt": operator.lt,
    "gt": operator.gt, "le": operator.le, "ge": operator.ge,
}
_MATH_OPS = {
    "add": operator.add, "sub": operator.sub, "mul": operator.mul,
    "truediv": operator.truediv,
}
_LOGICAL_OPS = {"or": operator.or_, "and": operator.and_, "xor": operator.xor}


def _table(columns, row_counts, names, ctx):
    from .table import Table

    return Table(tuple(columns), row_counts, tuple(names), ctx)


def _result_col(data: jax.Array, validity: jax.Array, dt: dtypes.DataType) -> Column:
    if data.dtype == jnp.bool_:
        data = data & validity
    else:
        data = jnp.where(validity, data, jnp.zeros((), data.dtype))
    return Column(data, validity, None, dt)


#: a string scalar's words come in fours (32 bytes), so that scalars of
#: most lengths have one shape: a stage program that takes the words as an
#: operand (plan/expr.py) is then the same program for all of them
_SCALAR_WORDS_STEP = 4


def string_scalar_words(value: str) -> np.ndarray:
    """``value`` in the packed big-endian ``uint64`` word encoding of
    ``keys.pack_string_words``, zero-padded to a multiple of
    ``_SCALAR_WORDS_STEP`` words."""
    enc = value.encode("utf-8")
    step = 8 * _SCALAR_WORDS_STEP
    padded = np.zeros((max(1, -(-len(enc) // step)) * step,), np.uint8)
    padded[:len(enc)] = np.frombuffer(enc, np.uint8)
    svals = padded.reshape(-1, 8).astype(np.uint64)
    shifts = np.array([56, 48, 40, 32, 24, 16, 8, 0], np.uint64)
    return (svals << shifts).sum(axis=1, dtype=np.uint64)


def _string_word_compare(col: Column, swords, op_name: str) -> jax.Array:
    """Lexicographic compare of a string column against a scalar's words
    (``string_scalar_words``; host values or a traced operand), on the
    packed big-endian word encoding (reference compares through arrow
    compute / object loops, compute.pyx:92-153; here it is vectorized).
    Where the scalar is longer than the column's padded width, equal-prefix
    rows compare less-than: the column's missing words are zeros."""
    from .ops import keys as keys_mod

    words = keys_mod.pack_string_words(col.data)
    lt = jnp.zeros(col.data.shape[:1], bool)
    gt = jnp.zeros(col.data.shape[:1], bool)
    for i in range(max(len(words), len(swords))):
        w = words[i] if i < len(words) else jnp.zeros_like(words[0])
        s = jnp.uint64(swords[i]) if i < len(swords) else jnp.uint64(0)
        undecided = ~(lt | gt)
        lt = lt | (undecided & (w < s))
        gt = gt | (undecided & (w > s))
    eq = ~(lt | gt)
    return {"eq": eq, "ne": ~eq, "lt": lt, "gt": gt,
            "le": lt | eq, "ge": gt | eq}[op_name]


def _col_compare(col: Column, other, op_name: str, other_col: Optional[Column]) -> Column:
    op = _CMP_OPS[op_name]
    if other_col is not None:
        if col.is_string != other_col.is_string:
            raise CylonError(Code.Invalid, "cannot compare string and numeric")
        if col.is_string:
            raise CylonError(Code.Invalid,
                             "string column-vs-column compare not supported")
        data = op(col.data, other_col.data)
        validity = col.validity & other_col.validity
        return _result_col(data, validity, dtypes.bool_)
    if isinstance(other, str):
        other = string_scalar_words(other)
    if getattr(other, "ndim", 0) == 1:  # a string scalar's words
        if not col.is_string:
            raise CylonError(Code.Invalid, f"cannot compare {col.dtype} to str")
        data = _string_word_compare(col, other, op_name)
        return _result_col(data, col.validity, dtypes.bool_)
    if col.is_string:
        raise CylonError(Code.Invalid, "cannot compare string column to number")
    # rely on jnp weak-type promotion: int column vs 2.5 compares in float
    data = op(col.data, other)
    return _result_col(data, col.validity, dtypes.bool_)


def _col_math(col: Column, other, op_name: str, other_col: Optional[Column]) -> Column:
    if col.is_string or (other_col is not None and other_col.is_string):
        raise CylonError(Code.Invalid, "arithmetic on string columns")
    op = _MATH_OPS[op_name]
    if other_col is not None:
        validity = col.validity & other_col.validity
        a, b = col.data, other_col.data
        if op_name == "truediv":
            a = a.astype(jnp.result_type(a.dtype, jnp.float32))
            validity = validity & (b != 0)
            b = jnp.where(b == 0, jnp.ones((), b.dtype), b)
        data = op(a, b)
    else:
        # division guard (reference: compute.pyx:215-239 division_op raises
        # on a zero divisor)
        if op_name == "truediv" and not isinstance(other, jax.Array) and other == 0:
            raise CylonError(Code.Invalid, "division by zero")
        a = col.data
        if op_name == "truediv":
            a = a.astype(jnp.result_type(a.dtype, jnp.float32))
        # weak-type promotion: int column + 2.5 promotes to float
        data = op(a, other)
        validity = col.validity
    return _result_col(data, validity, dtypes.from_numpy_dtype(data.dtype))


def _broadcast_other(table, other):
    """Resolve ``other`` into per-column partners (None = scalar path)."""
    from .table import Table

    if isinstance(other, Table):
        if len(other.columns) != len(table.columns):
            raise CylonError(Code.Invalid, "column count mismatch")
        if other.capacity != table.capacity:
            raise CylonError(Code.Invalid, "row capacity mismatch")
        return other.columns
    return None


def _elementwise(table, other, op_name: str, kernel: Callable):
    others = _broadcast_other(table, other)
    cols = []
    for i, c in enumerate(table.columns):
        oc = others[i] if others is not None else None
        cols.append(kernel(c, other, op_name, oc))
    return _table(cols, table.row_counts, table.names, table.ctx)


# -- public op surface (reference: compute.pyx cpdef functions) -------------

def compare(table, other, op_name: str):
    return _elementwise(table, other, op_name, _col_compare)


def math_op(table, other, op_name: str):
    """reference: compute.pyx:240-274 math_op/add/subtract/multiply/divide."""
    return _elementwise(table, other, op_name, _col_math)


def add(table, value):
    return math_op(table, value, "add")


def subtract(table, value):
    return math_op(table, value, "sub")


def multiply(table, value):
    return math_op(table, value, "mul")


def divide(table, value):
    return math_op(table, value, "truediv")


def logical_op(table, other, op_name: str):
    """reference: table.pyx:1375-1442 __or__/__and__ (bool tables only)."""
    others = _broadcast_other(table, other)
    op = _LOGICAL_OPS[op_name]
    cols = []
    for i, c in enumerate(table.columns):
        if c.dtype.type != dtypes.Type.BOOL:
            raise CylonError(Code.Invalid,
                             f"logical op on non-bool column {table.names[i]}")
        if others is not None:
            oc = others[i]
            if oc.dtype.type != dtypes.Type.BOOL:
                raise CylonError(Code.Invalid, "logical op on non-bool column")
            data = op(c.data, oc.data)
            validity = c.validity & oc.validity
        else:
            data = op(c.data, bool(other))
            validity = c.validity
        cols.append(_result_col(data, validity, dtypes.bool_))
    return _table(cols, table.row_counts, table.names, table.ctx)


def invert(table):
    """reference: compute.pyx:174-193 (bool tables only)."""
    cols = []
    for i, c in enumerate(table.columns):
        if c.dtype.type != dtypes.Type.BOOL:
            raise CylonError(Code.Invalid,
                             f"invert on non-bool column {table.names[i]}")
        cols.append(_result_col(~c.data, c.validity, dtypes.bool_))
    return _table(cols, table.row_counts, table.names, table.ctx)


def neg(table):
    """reference: compute.pyx:194-214."""
    cols = []
    for c in table.columns:
        if c.is_string:
            raise CylonError(Code.Invalid, "neg on string column")
        cols.append(_result_col(-c.data, c.validity, c.dtype))
    return _table(cols, table.row_counts, table.names, table.ctx)


def is_null(table):
    """bool table: True where value is missing (reference: compute.pyx:158-173
    is_null, table.pyx:1736 isnull).  Padding rows read False."""
    cols = []
    for c in table.columns:
        live = _live(table, c)
        cols.append(Column((~c.validity) & live,
                           jnp.ones(c.validity.shape, bool), None, dtypes.bool_))
    return _table(cols, table.row_counts, table.names, table.ctx)


def fillna(table, fill_value: Scalar):
    """reference: table.pyx:1653-1684."""
    cols = []
    for c in table.columns:
        # only fill type-compatible columns; others pass through unchanged
        # (pandas fillna semantics)
        if c.is_string != isinstance(fill_value, str):
            cols.append(c)
            continue
        if c.is_string:
            enc = np.frombuffer(fill_value.encode("utf-8"), np.uint8)
            width = c.data.shape[1]
            if len(enc) > width:
                raise CylonError(Code.Invalid,
                                 f"fill string longer than column width {width}")
            row = np.zeros((width,), np.uint8)
            row[:len(enc)] = enc
            data = jnp.where(c.validity[:, None], c.data, jnp.asarray(row))
            lengths = jnp.where(c.validity, c.lengths, len(enc))
            cols.append(Column(data, jnp.ones(c.validity.shape, bool), lengths,
                               c.dtype))
        else:
            data = jnp.where(c.validity, c.data,
                             jnp.asarray(fill_value, c.data.dtype))
            cols.append(Column(data, jnp.ones(c.validity.shape, bool), None,
                               c.dtype))
    # padding rows of filled columns must stay zeroed/invalid for kernels
    return _mask_padding(_table(cols, table.row_counts, table.names, table.ctx))


def where(table, condition, other: Optional[Scalar] = None):
    """Keep values where ``condition`` holds, else ``other`` (null when
    ``other`` is None) — reference: table.pyx:1685-1735."""
    from .table import Table

    if not isinstance(condition, Table):
        raise CylonError(Code.Invalid, "where() condition must be a Table")
    masks = condition.columns
    if len(masks) != len(table.columns):
        raise CylonError(Code.Invalid, "condition column count mismatch")
    cols = []
    for c, m in zip(table.columns, masks):
        if m.dtype.type != dtypes.Type.BOOL:
            raise CylonError(Code.Invalid, "condition must be boolean")
        keep = m.data & m.validity
        if other is None:
            validity = c.validity & keep
            data = c.data
        else:
            if c.is_string:
                raise CylonError(Code.Invalid, "where(other=) on string column")
            # mask-False rows take `other` unconditionally, including rows
            # that were null (reference: table.pyx where(); pandas semantics)
            validity = c.validity | ~keep
            data = jnp.where(keep, c.data, jnp.asarray(other, c.data.dtype))
        cols.append(_result_col(data, validity, c.dtype) if not c.is_string
                    else Column(jnp.where(validity[:, None], c.data, 0),
                                validity, jnp.where(validity, c.lengths, 0),
                                c.dtype))
    # where(other=) marks mask-False rows valid — re-invalidate padding rows
    # so kernels that trust validity never see phantom `other` values
    return _mask_padding(_table(cols, table.row_counts, table.names, table.ctx))


def is_in(table, values: Sequence, skip_null: bool = True):
    """Membership test per element (reference: compute.pyx:489-511 is_in,
    table.pyx:2100-2146 isin)."""
    vals = list(values)
    null_in_vals = any(v is None for v in vals)
    cols = []
    for c in table.columns:
        live = _live(table, c)
        if c.is_string:
            svals = [v for v in vals if isinstance(v, str)]
            hit = jnp.zeros(c.data.shape[:1], bool)
            for s in svals:
                hit = hit | _string_word_compare(
                    c, string_scalar_words(s), "eq")
        else:
            nums = [v for v in vals if not isinstance(v, str) and v is not None]
            if nums:
                # jnp.isin promotes, so 2.5 never falsely matches int 2
                hit = jnp.isin(c.data, jnp.asarray(np.asarray(nums)))
            else:
                hit = jnp.zeros(c.data.shape[:1], bool)
        hit = hit & c.validity
        if not skip_null and null_in_vals:
            hit = hit | (~c.validity)
        hit = hit & live
        cols.append(_result_col(hit, jnp.ones_like(c.validity), dtypes.bool_))
    return _table(cols, table.row_counts, table.names, table.ctx)


def drop_na(table, how: str = "any", axis: int = 0):
    """reference: compute.pyx:512-587 drop_na / table.pyx:2028-2099 dropna."""
    if axis == 1:
        counts = [(int(jnp.sum(~c.validity & _live(table, c))), i)
                  for i, c in enumerate(table.columns)]
        if how == "any":
            keep = [i for n, i in counts if n == 0]
        elif how == "all":
            live_total = table.row_count
            # a zero-row table has no all-null column (pandas keeps all)
            keep = [i for n, i in counts if live_total == 0 or n < live_total]
        else:
            raise CylonError(Code.Invalid, f"bad how={how!r}")
        return table.project(keep)
    if how not in ("any", "all"):
        raise CylonError(Code.Invalid, f"bad how={how!r}")

    names = table.names
    # stable predicate per (how, names) so the shard-map jit cache hits
    # (table.select keys on predicate identity)
    key = (how, names)
    predicate = _DROPNA_PREDICATES.get(key)
    if predicate is None:
        def predicate(env, names=names, how=how):
            ms = [env.validity(n) for n in names]
            acc = ms[0]
            for m in ms[1:]:
                acc = (acc & m) if how == "any" else (acc | m)
            return acc

        _DROPNA_PREDICATES[key] = predicate
    return table.select(predicate)


_DROPNA_PREDICATES: dict = {}


def _live(table, col: Column) -> jax.Array:
    cap = col.data.shape[0]
    if table.num_shards == 1:
        return jnp.arange(cap, dtype=jnp.int32) < table.row_counts[0]
    scap = cap // table.num_shards
    pos = jnp.arange(cap, dtype=jnp.int32) % scap
    return pos < jnp.repeat(table.row_counts, scap)


def _mask_padding(table):
    cols = []
    for c in table.columns:
        live = _live(table, c)
        validity = c.validity & live
        if c.is_string:
            data = jnp.where(validity[:, None], c.data, 0)
            lengths = jnp.where(validity, c.lengths, 0)
            cols.append(Column(data, validity, lengths, c.dtype))
        else:
            if c.data.dtype == jnp.bool_:
                data = c.data & validity
            else:
                data = jnp.where(validity, c.data, jnp.zeros((), c.data.dtype))
            cols.append(Column(data, validity, None, c.dtype))
    return _table(cols, table.row_counts, table.names, table.ctx)


def unique(table):
    """Row-distinct table (reference: compute.pyx:276-284)."""
    return table.unique()


def nunique(table) -> int:
    """Distinct row count (reference: compute.pyx:285-287)."""
    return table.unique().row_count
