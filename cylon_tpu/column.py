"""Device-resident column.

TPU-native analog of the reference's ``cylon::Column`` (reference:
cpp/src/cylon/column.hpp:31-113) — a named, typed array — except that the
backing store is ``jax.Array`` buffers in TPU HBM instead of an
``arrow::ChunkedArray`` on the host heap.

Representation choices (TPU-first):

- Every column carries a static **capacity** (``data.shape[0]``); the number
  of *valid* rows is tracked by the owning Table.  Padding rows beyond the
  row count are zeroed.  This is what makes every relational kernel a
  static-shape XLA program: ops produce a new capacity + a new dynamic row
  count instead of dynamically-shaped arrays.
- Nulls are a ``bool[capacity]`` validity vector (True = present), the JAX
  rendering of Arrow's validity bitmap that the reference streams around
  (reference: cpp/src/cylon/arrow/arrow_all_to_all.cpp:105-107).
- STRING/BINARY columns are fixed-width padded byte matrices
  ``uint8[capacity, width]`` plus ``int32[capacity]`` lengths — TPU kernels
  need static shapes, so Arrow's offsets+bytes become pad-to-width on ingest
  and are re-ragged only at the host boundary.  Zero padding preserves
  bytewise lexicographic order, so sort/compare kernels can treat the byte
  matrix as the value.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes
from .dtypes import DataType, Type
from .obs import metrics as obs_metrics
from .obs import span as obs_span
from .status import Code, CylonError

DEFAULT_STRING_WIDTH = 32


def max_string_width() -> int:
    """HBM guard: the widest byte matrix a string column may ingest with
    (capacity x width bytes live in device memory).  One oversized cell
    otherwise inflates the whole column — the overflow policy is an error
    naming the cell, not silent truncation; callers that really want wide
    rows pass ``string_width=`` explicitly or raise the env cap."""
    from . import config

    return int(config.knob("CYLON_TPU_MAX_STRING_WIDTH"))


def _check_width(needed: int, explicit: Optional[int]) -> None:
    cap = max_string_width()
    if needed > cap and (explicit is None or needed > explicit):
        raise CylonError(
            Code.Invalid,
            f"string cell of {needed} bytes exceeds the column width cap "
            f"{cap} (HBM = capacity x width); pass string_width>={needed} "
            f"or raise CYLON_TPU_MAX_STRING_WIDTH to ingest it")


@jax.tree_util.register_dataclass
@dataclass
class Column:
    """One typed column of device buffers.

    data:      [capacity] (fixed width) or [capacity, width] uint8 (strings)
    validity:  bool[capacity]; True = value present
    lengths:   int32[capacity] byte lengths (string-like only, else None)
    dtype:     logical type (static / aux data for jit)
    """

    data: jax.Array
    validity: jax.Array
    lengths: Optional[jax.Array] = None
    dtype: DataType = field(default=dtypes.int64, metadata={"static": True})

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def is_string(self) -> bool:
        return dtypes.is_string_like(self.dtype)

    @property
    def string_width(self) -> int:
        return int(self.data.shape[1]) if self.data.ndim == 2 else 0

    def with_capacity(self, capacity: int) -> "Column":
        """Pad (with zeros/False) or truncate buffers to a new capacity."""
        cap = self.capacity
        if capacity == cap:
            return self
        if capacity < cap:
            return Column(self.data[:capacity], self.validity[:capacity],
                          None if self.lengths is None else self.lengths[:capacity],
                          self.dtype)
        pad = capacity - cap
        data = jnp.concatenate(
            [self.data, jnp.zeros((pad,) + self.data.shape[1:], self.data.dtype)])
        validity = jnp.concatenate([self.validity, jnp.zeros((pad,), bool)])
        lengths = None
        if self.lengths is not None:
            lengths = jnp.concatenate([self.lengths, jnp.zeros((pad,), jnp.int32)])
        return Column(data, validity, lengths, self.dtype)

    def take(self, indices: jax.Array, valid_mask: Optional[jax.Array] = None) -> "Column":
        """Gather rows by index; optionally AND validity with ``valid_mask``
        (used by outer joins to null-fill non-matching rows, the analog of the
        reference's -1 index fills, cpp/src/cylon/join/join.cpp:179-235)."""
        data = jnp.take(self.data, indices, axis=0, mode="clip")
        validity = jnp.take(self.validity, indices, axis=0, mode="clip")
        if valid_mask is not None:
            validity = validity & valid_mask
            if not dtypes.is_string_like(self.dtype):
                data = jnp.where(validity, data, jnp.zeros((), data.dtype))
            else:
                data = jnp.where(validity[:, None], data, jnp.zeros((), data.dtype))
        lengths = None
        if self.lengths is not None:
            lengths = jnp.take(self.lengths, indices, axis=0, mode="clip")
            if valid_mask is not None:
                lengths = jnp.where(validity, lengths, 0)
        return Column(data, validity, lengths, self.dtype)

    def masked(self, valid_mask: jax.Array) -> "Column":
        """The rows under ``valid_mask`` and null elsewhere, as
        ``take(indices, valid_mask)`` leaves them: a null reads zero in
        data and lengths."""
        validity = self.validity & valid_mask
        data = jnp.where(validity if self.data.ndim == 1 else
                         validity[:, None], self.data,
                         jnp.zeros((), self.data.dtype))
        lengths = None if self.lengths is None else jnp.where(
            validity, self.lengths, 0)
        return Column(data, validity, lengths, self.dtype)


# ---------------------------------------------------------------------------
# Host-boundary constructors / exporters
# ---------------------------------------------------------------------------

def _next_capacity(n: int, capacity: Optional[int]) -> int:
    if capacity is not None:
        if capacity < n:
            raise ValueError(f"capacity {capacity} < row count {n}")
        return capacity
    return max(8, n)


def _u_trailing_nul(values: np.ndarray) -> bool:
    """True if any element of a U-dtype array ends in NUL codepoints (the
    numpy U/S item-access convention strips them, so the vectorized
    encoder would silently drop those characters)."""
    n = len(values)
    w = values.dtype.itemsize // 4
    if n == 0 or w == 0:
        return False
    raw = np.ascontiguousarray(values).view(np.uint32).reshape(n, w)
    nz = raw != 0
    exact = np.where(nz.any(axis=1), w - np.argmax(nz[:, ::-1], axis=1), 0)
    return bool((exact != np.char.str_len(values)).any())


def _encode_rows_exact(values, missing):
    """Per-row exact encoder (bytes kept verbatim, str utf-8-encoded) —
    the fallback for inputs the vectorized path cannot represent."""
    enc_list = [b"" if missing[i]
                else (bytes(v) if isinstance(v, (bytes, bytearray))
                      else str(v).encode("utf-8"))
                for i, v in enumerate(values)]
    w = max(1, max(map(len, enc_list)))
    lens = np.array([len(b) for b in enc_list], np.int32)
    return np.asarray(enc_list, f"S{w}"), missing, lens


def _encode_strings(values: np.ndarray):
    """(S-dtype encoded array, missing mask, exact lens or None) for a
    U/S/object string array — vectorized (np.char) except bytes mixes and
    values with trailing NULs, which take the exact per-row path.
    ``lens=None`` means np.char.str_len is exact."""
    n = len(values)
    if n == 0:
        return np.zeros((0,), "S1"), np.zeros((0,), bool), None
    if values.dtype.kind == "S":
        lens = np.array([len(v) for v in values], np.int32)  # NUL-exact
        return np.ascontiguousarray(values), np.zeros((n,), bool), lens
    if values.dtype.kind == "U":
        if _u_trailing_nul(values):
            return _encode_rows_exact(values, np.zeros((n,), bool))
        return np.char.encode(values, "utf-8"), np.zeros((n,), bool), None
    # object column: None/NaN are nulls (pandas missing-value convention)
    import pandas as pd

    missing = np.asarray(pd.isna(values), bool)
    if any(isinstance(v, (bytes, bytearray))
           or (isinstance(v, str) and v.endswith("\x00")) for v in values):
        return _encode_rows_exact(values, missing)
    filled = values.copy()
    filled[missing] = ""
    return np.char.encode(filled.astype("U"), "utf-8"), missing, None


def from_numpy(values: np.ndarray, *, validity: Optional[np.ndarray] = None,
               capacity: Optional[int] = None,
               string_width: int = DEFAULT_STRING_WIDTH,
               dtype: Optional[DataType] = None) -> Column:
    """Build a Column from a host numpy array (object/str arrays become
    padded byte matrices)."""
    values = np.asarray(values)
    n = len(values)
    cap = _next_capacity(n, capacity)
    if values.dtype.kind in ("U", "S", "O"):
        enc, missing, exact_lens = _encode_strings(values)
        obs = enc.dtype.itemsize if n else 0
        _check_width(obs, string_width)
        width = max(string_width, obs)
        mat = np.zeros((cap, width), np.uint8)
        lens = np.zeros((cap,), np.int32)
        if n and obs:
            mat[:n, :obs] = np.ascontiguousarray(enc).view(np.uint8).reshape(n, obs)
            lens[:n] = (np.char.str_len(enc) if exact_lens is None
                        else exact_lens)
        valid = np.zeros((cap,), bool)
        valid[:n] = ~missing if validity is None else validity[:n]
        dt = dtype or dtypes.string
        return Column(jnp.asarray(mat), jnp.asarray(valid), jnp.asarray(lens), dt)
    if values.dtype.kind == "M":
        # datetime64 -> int64 microseconds (Arrow timestamp physical layout)
        if validity is None:
            validity = ~np.isnat(values)
        values = values.astype("datetime64[us]").astype(np.int64)
        dt = dtype or dtypes.timestamp("us")
    else:
        dt = dtype or dtypes.from_numpy_dtype(values.dtype)
    if validity is None and values.dtype.kind == "f":
        # NaN = missing, matching Arrow/pandas ingestion semantics
        validity = ~np.isnan(values)
    buf = np.zeros((cap,), values.dtype)
    buf[:n] = values
    valid = np.zeros((cap,), bool)
    valid[:n] = True if validity is None else validity[:n]
    buf[:n] = np.where(valid[:n], buf[:n], np.zeros((), values.dtype))
    return Column(jnp.asarray(buf), jnp.asarray(valid), None, dt)


def from_native_buffers(data: np.ndarray, validity: Optional[np.ndarray],
                        lengths: Optional[np.ndarray] = None, *,
                        capacity: Optional[int] = None,
                        string_width: Optional[int] = None) -> Column:
    """Build a Column from the native (C++) layer's Column-shaped buffers —
    1-D fixed-width data, or 2-D uint8 byte matrix + lengths for strings
    (cylon_tpu/native csv_read / registry_get output).  The buffers already
    match the device layout, so this is pad-to-capacity + device_put only."""
    n = len(data)
    cap = _next_capacity(n, capacity)
    if data.ndim == 2:  # string byte matrix
        w = data.shape[1]
        if string_width and string_width > w:
            w = string_width
        mat = np.zeros((cap, w), np.uint8)
        mat[:n, : data.shape[1]] = data
        lens = np.zeros((cap,), np.int32)
        if lengths is not None:
            lens[:n] = np.minimum(lengths, w)
        valid = np.zeros((cap,), bool)
        valid[:n] = True if validity is None else validity[:n]
        return Column(jnp.asarray(mat), jnp.asarray(valid), jnp.asarray(lens),
                      dtypes.string)
    dt = dtypes.from_numpy_dtype(data.dtype)
    buf = np.zeros((cap,), data.dtype)
    buf[:n] = data
    valid = np.zeros((cap,), bool)
    valid[:n] = True if validity is None else validity[:n]
    buf[:n] = np.where(valid[:n], buf[:n], np.zeros((), data.dtype))
    return Column(jnp.asarray(buf), jnp.asarray(valid), None, dt)


def from_arrow(arr, *, capacity: Optional[int] = None,
               string_width: int = DEFAULT_STRING_WIDTH) -> Column:
    """Build a Column from a pyarrow Array/ChunkedArray (the ingest bridge the
    reference does via arrow memory directly, cpp/src/cylon/table.cpp
    FromArrowTable)."""
    import pyarrow as pa

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        # dictionary-encoded columns decode at the boundary: the device
        # layout is the padded byte matrix either way, and every kernel
        # (hash/sort/compare) operates on materialized values
        arr = arr.dictionary_decode()
    dt = dtypes.from_arrow_type(arr.type)
    n = len(arr)
    validity = np.ones((n,), bool)
    if arr.null_count:
        validity = np.asarray(arr.is_valid())
    if dtypes.is_string_like(dt):
        import pyarrow as pa

        cap = _next_capacity(n, capacity)
        if pa.types.is_fixed_size_binary(arr.type):
            w = arr.type.byte_width
            data = np.frombuffer(arr.buffers()[1], np.uint8)
            lo = arr.offset * w
            offsets = np.arange(lo, lo + (n + 1) * w, w, np.int64)
            lens_np = np.full((n,), w, np.int64)
        else:
            off_np = (np.int64 if pa.types.is_large_string(arr.type)
                      or pa.types.is_large_binary(arr.type) else np.int32)
            bufs = arr.buffers()
            offsets = np.frombuffer(bufs[1], off_np)[
                arr.offset: arr.offset + n + 1].astype(np.int64)
            data = (np.frombuffer(bufs[2], np.uint8) if bufs[2] is not None
                    else np.zeros((0,), np.uint8))
            lens_np = np.diff(offsets)
        # null slots hold Arrow-spec-undefined bytes: zero their lengths so
        # the copy below skips them and the matrix rows stay zeroed (the
        # module invariant every kernel relies on)
        lens_np = np.where(validity[:n], lens_np, 0)
        obs = int(lens_np.max()) if n else 0
        _check_width(obs, string_width)
        width = max(string_width, obs)
        mat = np.zeros((cap, width), np.uint8)
        total = int(lens_np.sum())
        if total:
            # vectorized ragged copy with O(total payload) temporaries (a
            # full (n, obs) index matrix would dwarf the column itself)
            starts = np.cumsum(lens_np) - lens_np
            within = np.arange(total, dtype=np.int64) - np.repeat(starts,
                                                                  lens_np)
            src = np.repeat(offsets[:-1], lens_np) + within
            dst_row = np.repeat(np.arange(n, dtype=np.int64), lens_np)
            mat[: n].reshape(-1)[dst_row * width + within] = data[src]
        lens = np.zeros((cap,), np.int32)
        lens[:n] = lens_np
        valid = np.zeros((cap,), bool)
        valid[:n] = validity[:n]
        return Column(jnp.asarray(mat), jnp.asarray(valid), jnp.asarray(lens),
                      dt)
    if arr.null_count:
        # fill nulls BEFORE to_numpy: a nullable int64 otherwise detours
        # through float64 + NaN, silently rounding values above 2^53
        if pa.types.is_boolean(arr.type):
            arr = arr.fill_null(False)
        elif pa.types.is_integer(arr.type) or pa.types.is_floating(arr.type):
            arr = arr.fill_null(0)
    np_vals = arr.to_numpy(zero_copy_only=False)
    if np_vals.dtype.kind in ("O", "m", "M") or np_vals.dtype == object:
        np_vals = np.asarray(arr.cast(dtypes.to_arrow_type(dt)).to_numpy(zero_copy_only=False))
        if np_vals.dtype == object:
            np_vals = np.array([0 if v is None else v for v in np_vals],
                               dtype=dt.numpy_dtype())
    np_vals = np.ascontiguousarray(np_vals)
    if np_vals.dtype.kind == "f" and arr.null_count:
        np_vals = np.nan_to_num(np_vals, copy=False)
    if np_vals.dtype != dt.numpy_dtype():
        np_vals = np_vals.astype(dt.numpy_dtype())
    return from_numpy(np_vals, validity=validity, capacity=capacity, dtype=dt)


def _bytes_rows(mat: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """object[n] of per-row ``bytes`` from a padded byte matrix —
    vectorized via an S-dtype view (trailing NULs are padding by
    construction); the rare row whose payload genuinely ends in NUL bytes
    is fixed up individually."""
    n, w = mat.shape
    if n == 0 or w == 0:
        return np.full((n,), b"", object)
    sview = np.ascontiguousarray(mat).view(f"S{w}")[:, 0]
    out = sview.astype(object)
    mismatch = np.nonzero(np.char.str_len(sview) != lens)[0]
    for i in mismatch:
        out[i] = mat[i, : lens[i]].tobytes()
    return out


def _decode_rows(rows: np.ndarray, valid: np.ndarray,
                 errors: str = "strict") -> np.ndarray:
    """object[n] of decoded str (or raw bytes where utf-8 fails under
    ``errors='strict'``); invalid rows become None.  Vectorized np.char
    decode, with a per-row path only for invalid utf-8 or payloads ending
    in NUL (the S-dtype round trip would strip them)."""
    n = rows.shape[0]
    out = np.empty((n,), object)
    slow = (np.array([bool(v) and r.endswith(b"\x00")
                      for v, r in zip(valid, rows)], bool)
            if n else np.zeros((0,), bool))
    fast = valid & ~slow
    try:
        if fast.any():
            out[fast] = np.char.decode(rows[fast].astype("S"), "utf-8",
                                       errors).astype(object)
    except UnicodeDecodeError:
        fast = np.zeros_like(valid)
    for i in np.nonzero(valid & ~fast)[0]:
        b = rows[i]
        try:
            out[i] = b.decode("utf-8", errors)
        except UnicodeDecodeError:
            out[i] = b
    out[~valid] = None
    return out


def wide_wait(buffers):
    """Span ``table.fetch.d2h.wide`` round the host's wait for ``buffers``
    where any of its leaves has 8-byte elements, else nothing: a 64-bit
    buffer leaves a chip far more slowly than a 32-bit one.  Where every
    copy is in flight together (``Table._live_shard_rows``) the buffers
    are waited for in flatten order, so the span is the host's wait on a
    64-bit buffer, not its transfer: what earlier buffers' waits already
    covered is not in it."""
    if any(np.dtype(b.dtype).itemsize == 8
           for b in jax.tree_util.tree_leaves(buffers)):
        return obs_span("table.fetch.d2h.wide")
    return contextlib.nullcontext()


def fetch_d2h(buffers, n: Optional[int] = None, get=jax.device_get):
    """One blocking device->host copy of a fetch, as NumPy: a pytree of
    buffers, or the first ``n`` rows of one buffer (the device slice is
    part of the copy).  Span ``table.fetch.d2h``, and inside it
    ``table.fetch.d2h.wide`` (``wide_wait``) where a buffer is 64-bit; the
    bytes that arrived add to counter ``table.fetch.bytes``.  A buffer
    that is NumPy already (a sharded table's fetched rows,
    ``Table._fetched_columns``) was counted when it arrived: its rows come
    back as they are, with no span and nothing counted."""
    if isinstance(buffers, np.ndarray):
        return buffers if n is None else buffers[:n]
    with obs_span("table.fetch.d2h"):
        with wide_wait(buffers):
            out = get(buffers if n is None else buffers[:n])
        obs_metrics.counter_add(
            "table.fetch.bytes",
            sum(a.nbytes for a in jax.tree_util.tree_leaves(out)))
    return out


def _fetch_buffers(col: Column, n: int):
    """validity, data and lengths (None for a fixed-width column) of the
    first ``n`` rows on the host, each copied by ``fetch_d2h``: what
    ``to_numpy`` / ``to_arrow`` convert, in span ``table.fetch.convert``
    after the copies, so that span holds no ``table.fetch.d2h``."""
    return (fetch_d2h(col.validity, n), fetch_d2h(col.data, n),
            None if col.lengths is None else fetch_d2h(col.lengths, n))


def to_numpy(col: Column, row_count: int):
    """Export valid rows to host. Strings come back as an object array of
    ``bytes`` decoded to str when valid utf-8."""
    valid, vals, lens = _fetch_buffers(col, int(row_count))
    with obs_span("table.fetch.convert"):
        if col.is_string:
            return _decode_rows(_bytes_rows(vals, lens), valid)
        ndt = col.dtype.numpy_dtype()
        if (vals.dtype != ndt and vals.dtype.kind in "iu"
                and np.dtype(ndt).kind in "iu"):
            vals = vals.astype(ndt)  # narrow-mode count buffers widen at export
        if valid.all():
            return vals
        out = vals.astype(object)
        out[~valid] = None
        return out


def to_arrow(col: Column, row_count: int):
    """Export valid rows to a pyarrow Array (host boundary, re-ragging the
    padded byte matrices back into offsets+bytes)."""
    import pyarrow as pa

    valid, vals, lens = _fetch_buffers(col, int(row_count))
    with obs_span("table.fetch.convert"):
        mask = None if valid.all() else ~valid
        at = dtypes.to_arrow_type(col.dtype)
        if col.is_string:
            rows = _bytes_rows(vals, lens)
            if col.dtype.type == Type.STRING:
                # errors='replace' never raises, so every valid row decodes
                vals = _decode_rows(rows, valid, errors="replace")
                vals[~valid] = ""  # placeholder under the null mask
            else:
                vals = rows
        return pa.array(vals, type=at, mask=mask)
