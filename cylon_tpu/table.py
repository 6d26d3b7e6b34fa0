"""The Table: a columnar, mesh-partitioned relational table in TPU HBM.

TPU-native analog of ``cylon::Table`` (reference: cpp/src/cylon/table.hpp:
43-417, table.cpp) plus the distributed operator layer L4 dispatch
(DistributedJoin/Union/Subtract/Intersect/Sort/Unique/GroupBy, table.cpp:
313-1047).  Key representation differences, chosen for XLA:

- A Table is a pytree of ``jax.Array`` column buffers with **static
  capacity** and a dynamic per-shard row count, instead of host
  ``arrow::Table`` chunks.  All relational kernels are static-shape jit
  programs; only the row-count scalar is data-dependent.
- A distributed Table's buffers are one **global array sharded over the
  1-D device mesh** (axis ``'p'``) — shard i on device i plays the role of
  MPI rank i's local table.  Shard-local kernels run under ``jax.shard_map``;
  the shuffle/collective layer (cylon_tpu.parallel) replaces the MPI
  channel machinery wholesale.
- Valid rows are front-packed per shard: rows [0, row_counts[s]) of shard s
  are live, the rest is zeroed padding (sorts last, masks cheaply).
"""
from __future__ import annotations

import collections
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import column as column_mod
from . import dtypes
from .column import Column
from .config import JoinAlgorithm, JoinConfig, JoinType, SortOptions
from .context import PARTITION_AXIS, CylonContext, ctx_cache, default_context
from .obs import metrics as obs_metrics
from .obs import span as obs_span
from .ops import aggregates as agg_mod
from .ops import compact as compact_mod
from .ops import groupby as groupby_mod
from .ops import join as join_mod
from .ops import keys as keys_mod
from .ops import setops as setops_mod
from .ops import sort as sort_mod
from .ops import unique as unique_mod
from .ops.groupby import AggOp
from .status import Code, CylonError

ColumnRef = Union[int, str]


from .utils import pow2ceil as _pow2ceil


@jax.tree_util.register_dataclass
@dataclass
class Table:
    """columns: per-column device buffers (global arrays, sharded if
    distributed); row_counts: int32[num_shards] live-row count per shard;
    names/ctx: static metadata."""

    columns: Tuple[Column, ...]
    row_counts: jax.Array
    names: Tuple[str, ...] = field(metadata={"static": True})
    ctx: CylonContext = field(metadata={"static": True})

    # ------------------------------------------------------------------
    # shape / metadata
    # ------------------------------------------------------------------
    @property
    def num_shards(self) -> int:
        return int(self.row_counts.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.columns[0].data.shape[0]) if self.columns else 0

    @property
    def shard_capacity(self) -> int:
        return self.capacity // self.num_shards

    @property
    def row_count(self) -> int:
        return int(host_sync(jnp.sum(self.row_counts), "row_count"))

    @property
    def column_count(self) -> int:
        return len(self.columns)

    @property
    def column_names(self) -> List[str]:
        return list(self.names)

    @property
    def schema(self) -> List[Tuple[str, dtypes.DataType]]:
        return [(n, c.dtype) for n, c in zip(self.names, self.columns)]

    @property
    def shape(self) -> Tuple[int, int]:
        """(rows, columns) — reference: python/pycylon/data/table.pyx:981."""
        return (self.row_count, self.column_count)

    @property
    def context(self) -> CylonContext:
        """The owning context — reference: data/table.pyx:207 (the repo
        field is ``ctx``; this is the pycylon-named accessor)."""
        return self.ctx

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}:{c.dtype}" for n, c in zip(self.names, self.columns))
        return (f"Table[{self.row_count} rows x {self.column_count} cols | "
                f"shards={self.num_shards} cap={self.capacity}]({cols})")

    # ------------------------------------------------------------------
    # column reference resolution (pycylon table.pyx:226-415 accepts names
    # or indices everywhere)
    # ------------------------------------------------------------------
    def _resolve(self, ref: ColumnRef) -> int:
        if isinstance(ref, (int, np.integer)):
            i = int(ref)
            if not 0 <= i < len(self.columns):
                raise CylonError(Code.IndexError, f"column index {i} out of range")
            return i
        try:
            return self.names.index(ref)
        except ValueError:
            raise CylonError(Code.KeyError, f"no column named {ref!r}")

    def _resolve_many(self, refs) -> Tuple[int, ...]:
        if isinstance(refs, (int, np.integer, str)):
            refs = [refs]
        return tuple(self._resolve(r) for r in refs)

    # ------------------------------------------------------------------
    # shard-wise execution
    # ------------------------------------------------------------------
    def _local_like(self, columns, row_counts) -> "Table":
        return Table(tuple(columns), row_counts, self.names, self.ctx)

    def is_distributed(self) -> bool:
        return self.num_shards > 1

    # ------------------------------------------------------------------
    # constructors
    # ------------------------------------------------------------------
    @staticmethod
    def from_columns(cols: Dict[str, Column], row_count: int,
                     ctx: Optional[CylonContext] = None) -> "Table":
        ctx = ctx or default_context()
        names = tuple(cols.keys())
        return Table(tuple(cols.values()),
                     jnp.asarray([row_count], jnp.int32), names, ctx)

    @staticmethod
    def from_pydict(data: Dict[str, Sequence], ctx: Optional[CylonContext] = None,
                    capacity: Optional[int] = None) -> "Table":
        arrays = {k: np.asarray(v) for k, v in data.items()}
        return _table_from_numpy(arrays, ctx or default_context(), capacity)

    @staticmethod
    def from_pandas(df, ctx: Optional[CylonContext] = None,
                    capacity: Optional[int] = None) -> "Table":
        arrays = {}
        for name in df.columns:
            s = df[name]
            arrays[str(name)] = s.to_numpy()
        return _table_from_numpy(arrays, ctx or default_context(), capacity)

    @staticmethod
    def from_arrow(atable, ctx: Optional[CylonContext] = None,
                   capacity: Optional[int] = None) -> "Table":
        arrays = {name: atable.column(name) for name in atable.column_names}
        return _table_from_arrow(arrays, ctx or default_context(), capacity)

    @staticmethod
    def from_csv(paths, options=None, ctx: Optional[CylonContext] = None,
                 capacity: Optional[int] = None) -> "Table":
        """Read CSV file(s); a list of paths maps file i -> shard i
        (reference: Table::FromCSV, table.cpp:803-855)."""
        from . import io as io_mod

        return io_mod.read_csv(paths, options, ctx, capacity)

    @staticmethod
    def from_parquet(paths, options=None, ctx: Optional[CylonContext] = None,
                     capacity: Optional[int] = None) -> "Table":
        """reference: Table::FromParquet (table.cpp:1049-1116)."""
        from . import io as io_mod

        return io_mod.read_parquet(paths, options, ctx, capacity)

    def to_csv(self, path, options=None, per_shard: bool = False) -> None:
        """reference: Table::WriteCSV (table.cpp:243-256).  With
        ``per_shard=True``, ``path`` must contain a ``{shard}`` placeholder
        and each process-local shard is written to its own file — no
        gather, the scalable inverse of the list-of-paths read."""
        from . import io as io_mod

        io_mod.write_csv(self, path, options, per_shard=per_shard)

    def to_parquet(self, path, options=None, per_shard: bool = False) -> None:
        """reference: Table::WriteParquet (table.cpp:1118-1131); per-shard
        mode as in ``to_csv``."""
        from . import io as io_mod

        io_mod.write_parquet(self, path, options, per_shard=per_shard)

    @staticmethod
    def from_numpy(names: Sequence[str], arrays: Sequence[np.ndarray],
                   ctx: Optional[CylonContext] = None,
                   capacity: Optional[int] = None) -> "Table":
        return _table_from_numpy(dict(zip(names, arrays)), ctx or default_context(),
                                 capacity)

    # ------------------------------------------------------------------
    # exporters (host boundary)
    # ------------------------------------------------------------------
    def _gathered_columns(self) -> Tuple[List[Column], int]:
        """Live rows of every shard as one local DEVICE column set, for
        the callers that go on computing on them (the planner's ``limit``,
        ``DataFrame.<column>``): a one-shard table's own columns, else
        ``_fetched_columns`` down once and up once (span
        ``table.fetch.h2d``, counter ``table.fetch.h2d_bytes``: the live
        bytes).  The exporters upload nothing: ``_export_columns``."""
        if self.num_shards == 1:
            return (list(self.columns),
                    int(column_mod.fetch_d2h(self.row_counts)[0]))

        cols_h, total = self._fetched_columns()
        with obs_span("table.fetch.h2d"):
            out_cols = [jax.tree_util.tree_map(jnp.asarray, c)
                        for c in cols_h]
            obs_metrics.counter_add(
                "table.fetch.h2d_bytes",
                sum(b.nbytes for b in jax.tree_util.tree_leaves(cols_h)))
        return out_cols, total

    def _fetched_columns(self) -> Tuple[List[Column], int]:
        """Live rows of every shard of a sharded table, shard 0 … n-1, as
        HOST-backed columns (NumPy buffers, which ``column.to_numpy`` /
        ``to_arrow`` and the writers read in place) and their total.

        In one process every live row crosses to the host once
        (``_live_shard_rows``: device slices, all copies in flight
        together).  Across processes the shards live elsewhere, so the
        whole buffers come through one cross-process all-gather (the
        reference's analog is a gather-to-rank pattern over MPI) and are
        cut to their counts here.  Either way the blocking copies are span
        ``table.fetch.d2h`` (the waits for 64-bit buffers also
        ``table.fetch.d2h.wide``) and what arrived adds to
        ``table.fetch.bytes``; nothing is uploaded.  Putting a buffer's
        shard pieces into one array (the cross-process path's cut to the
        counts with it) is span ``table.fetch.assemble``, one a buffer,
        opened after that buffer's wait has closed."""
        cap = self.shard_capacity
        buffers, treedef = jax.tree_util.tree_flatten(self.columns)
        if jax.process_count() > 1:
            counts, whole = column_mod.fetch_d2h(
                (self.row_counts, buffers), get=_get_everywhere)
            counts = np.asarray(counts)
            # lazy, so the cut to the counts runs inside assemble's span
            pieces = ((np.asarray(w)[s * cap: s * cap + int(n)]
                       for s, n in enumerate(counts)) for w in whole)
        else:
            counts = np.asarray(column_mod.fetch_d2h(self.row_counts))
            pieces = ([rows[s] for s in range(self.num_shards)]
                      for rows in _live_shard_rows(buffers, counts, cap))

        def assemble(parts):
            with obs_span("table.fetch.assemble"):
                return np.concatenate(list(parts))

        # a buffer is assembled while the later ones are still on their way
        cols = jax.tree_util.tree_unflatten(
            treedef, [assemble(parts) for parts in pieces])
        return list(cols), int(counts.sum())

    def _export_columns(self) -> Tuple[List[Column], int]:
        """What an export to the host reads, and its row count: a
        one-shard table's own device columns (``column.to_numpy`` slices
        and copies them), a sharded table's live rows on the host."""
        if self.num_shards == 1:
            return self._gathered_columns()
        return self._fetched_columns()

    def _addressable_host_shards(self) -> List[Tuple[int, List[Column], int]]:
        """Host views of every shard whose device buffers live on this
        process: [(shard_id, columns, live_count)].

        The gather-free twin of ``_fetched_columns`` — on multi-host each
        process sees only its own shards, mirroring the reference's
        rank-local table writes (table.cpp:243-256 WriteCSV writes the
        calling rank's partition, never a gathered table).  The columns
        hold HOST (NumPy) buffers — a sharded table's cut to the live
        count (``_live_shard_rows``), a one-shard table's whole, and a
        writer cuts to the count either way; the copies are spans
        ``table.fetch.d2h`` (``table.fetch.d2h.wide`` inside them where a
        buffer is 64-bit), counted in ``table.fetch.bytes`` once, and
        nothing is uploaded."""
        with obs_span("table.fetch", shards=self.num_shards):
            counts = np.asarray(column_mod.fetch_d2h(self.row_counts,
                                                     get=_get_everywhere))
            if self.num_shards == 1:
                cols_h = column_mod.fetch_d2h(self.columns)
                cols = [Column(np.asarray(c.data), np.asarray(c.validity),
                               None if co.lengths is None
                               else np.asarray(c.lengths), co.dtype)
                        for co, c in zip(self.columns, cols_h)]
                return [(0, cols, int(counts[0]))]
            buffers, treedef = jax.tree_util.tree_flatten(self.columns)
            rows = list(_live_shard_rows(buffers, counts,
                                         self.shard_capacity))
            return [(sid, list(jax.tree_util.tree_unflatten(
                        treedef, [r[sid] for r in rows])), int(counts[sid]))
                    for sid in sorted(rows[0])]

    def to_arrow(self):
        import pyarrow as pa

        with obs_span("table.fetch", shards=self.num_shards):
            cols, total = self._export_columns()
            arrays = [column_mod.to_arrow(c, total) for c in cols]
        return pa.table(arrays, names=list(self.names))

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def to_pydict(self) -> Dict[str, list]:
        return self.to_arrow().to_pydict()

    def to_numpy(self) -> Dict[str, np.ndarray]:
        with obs_span("table.fetch", shards=self.num_shards):
            cols, total = self._export_columns()
            return {n: column_mod.to_numpy(c, total)
                    for n, c in zip(self.names, cols)}

    def print(self, limit: int = 20) -> None:
        """CSV-ish row dump (reference: table.cpp Print/PrintToOStream)."""
        print(self.to_string(limit))

    def to_string(self, row_limit: int = 10) -> str:
        """reference: pycylon Table.to_string (data/table.pyx:1602)."""
        d = self.to_pydict()
        names = list(d.keys())
        lines = [",".join(names)]
        n = min(row_limit, self.row_count)
        for i in range(n):
            lines.append(",".join(str(d[c][i]) for c in names))
        return "\n".join(lines)

    def show(self, row1: int = -1, row2: int = -1, col1: int = -1,
             col2: int = -1) -> None:
        """Print a row/column range; -1 bounds mean "to the end"
        (reference: data/table.pyx:101 show)."""
        if row1 == -1 and col1 == -1:
            self.print()
            return
        t = self
        if col1 != -1:
            hi_c = len(self.columns) if col2 == -1 else col2
            t = t.project(list(range(col1, hi_c)))
        lo = max(row1, 0)
        hi = t.row_count if row2 == -1 else min(row2, t.row_count)
        d = t.to_pydict()
        names = list(d.keys())
        print(",".join(names))
        for i in range(lo, hi):
            print(",".join(str(d[c][i]) for c in names))

    @staticmethod
    def from_list(col_names: Sequence[str], data_list: Sequence[Sequence],
                  ctx: Optional[CylonContext] = None) -> "Table":
        """Column-major lists (reference: data/table.pyx:811 from_list)."""
        if len(col_names) != len(data_list):
            raise CylonError(Code.Invalid,
                             f"{len(col_names)} names for {len(data_list)} columns")
        return Table.from_pydict(dict(zip(col_names, data_list)), ctx=ctx)

    def clear(self) -> None:
        """Drop all rows (reference: data/table.pyx:130 clear)."""
        self.row_counts = jnp.zeros_like(self.row_counts)

    def retain_memory(self, retain: bool) -> None:
        """Parity no-op (reference: data/table.pyx:136 — controls whether
        ops free their inputs; XLA arrays are freed by liveness, so there
        is nothing to toggle)."""

    def is_retain(self) -> bool:
        return True

    # -- index surface (reference: data/table.pyx:1977-2036) ----------
    @property
    def index(self):
        from .index import RangeIndex

        idx = getattr(self, "_index", None)
        return idx if idx is not None else RangeIndex(0, self.row_count)

    def set_index(self, key) -> None:
        """Route row lookups through ``key`` (reference: table.pyx:1992-2022
        — an Index object, a column name / list of names, or row_count
        labels).  Unlike the reference's stubbed loc engine
        (_libs/index.pyx get_loc: pass), the resulting index actually
        resolves ``loc`` lookups here."""
        from .index import process_index_by_value

        self._index = process_index_by_value(key, self)

    def reset_index(self, key=None) -> None:
        from .index import RangeIndex

        self._index = RangeIndex(0, self.row_count)

    @property
    def loc(self) -> "_TableIndexer":
        """Label-based row access over the active index: ``t.loc[label]``,
        ``t.loc[[l1, l2]]``, ``t.loc[lo:hi]`` (inclusive), boolean masks,
        and ``t.loc[rows, cols]`` column selection."""
        return _TableIndexer(self, "loc")

    @property
    def iloc(self) -> "_TableIndexer":
        """Position-based row access: int (negatives ok), slice, int
        list/array, boolean mask, and ``t.iloc[rows, cols]``."""
        return _TableIndexer(self, "iloc")

    def take_rows(self, positions) -> "Table":
        """Gather rows by position (host or device int array) into a new
        table — the compact/gather kernel behind loc/iloc."""
        if self.num_shards != 1:
            raise CylonError(Code.Invalid,
                             "row access requires a local (1-shard) table; "
                             "gather or repartition first")
        import numpy as _np

        idx = _np.asarray(positions, _np.int64)
        n = idx.shape[0]
        cap = max(8, n)
        pad_idx = jnp.asarray(_np.concatenate(
            [idx, _np.zeros(cap - n, _np.int64)]) if cap > n else idx,
            jnp.int32)
        from .ops import compact as compact_mod

        mask = compact_mod.live_mask(cap, jnp.asarray(n, jnp.int32))
        cols = tuple(c.take(pad_idx, valid_mask=mask) for c in self.columns)
        out = Table(cols, jnp.asarray([n], jnp.int32), self.names, self.ctx)
        from .index import (CategoricalIndex, ColumnIndex, Int64Index,
                            RangeIndex)

        idx_obj = getattr(self, "_index", None)
        if isinstance(idx_obj, CategoricalIndex):
            out._index = CategoricalIndex(
                _np.asarray(idx_obj.index_values, object)[idx])
        elif isinstance(idx_obj, ColumnIndex):
            vals = idx_obj.index_values
            if len(idx_obj.names) == 1:
                out._index = ColumnIndex(idx_obj.names[0],
                                         _np.asarray(vals)[idx])
            else:
                out._index = ColumnIndex(
                    list(idx_obj.names),
                    [_np.asarray(v)[idx] for v in vals])
        elif idx_obj is None or isinstance(idx_obj, RangeIndex):
            # positional labels survive selection (pandas: iloc[[5,7]]
            # keeps labels 5,7, not a fresh 0..n-1 range)
            labels = (_np.asarray(idx_obj.index_values) if idx_obj is not None
                      else _np.arange(self.row_count, dtype=_np.int64))
            out._index = Int64Index(labels[idx])
        else:  # NumericIndex and friends: gather their labels
            out._index = type(idx_obj)(_np.asarray(idx_obj.index_values)[idx])
        return out

    def isna(self) -> "Table":
        """alias of isnull (reference: data/table.pyx:1761)."""
        return self.isnull()

    def notna(self) -> "Table":
        """alias of notnull (reference: data/table.pyx:1808)."""
        return self.notnull()

    # ------------------------------------------------------------------
    # local relational ops (reference: table.hpp:241-417 free functions)
    # ------------------------------------------------------------------
    def project(self, refs) -> "Table":
        """Zero-copy column subset (reference: table.cpp:857-876)."""
        idx = self._resolve_many(refs)
        return Table(tuple(self.columns[i] for i in idx), self.row_counts,
                     tuple(self.names[i] for i in idx), self.ctx)

    def rename(self, mapping: Union[Dict[str, str], Sequence[str]]) -> "Table":
        if isinstance(mapping, dict):
            names = tuple(mapping.get(n, n) for n in self.names)
        else:
            if len(mapping) != len(self.names):
                raise CylonError(Code.Invalid, "rename length mismatch")
            names = tuple(mapping)
        return Table(self.columns, self.row_counts, names, self.ctx)

    def add_prefix(self, prefix: str) -> "Table":
        return self.rename([prefix + n for n in self.names])

    def add_suffix(self, suffix: str) -> "Table":
        return self.rename([n + suffix for n in self.names])

    def select(self, predicate) -> "Table":
        """Filter rows with a vectorized predicate over named column arrays
        (reference: table.cpp:491-520 Select with a row lambda; here the
        lambda sees whole columns and returns a bool mask — the jit-friendly
        contract)."""
        names, ctx = self.names, self.ctx

        def fn(t: Table) -> Table:
            cap = t.columns[0].data.shape[0]
            count = t.row_counts[0]
            env = _RowEnv({n: c for n, c in zip(names, t.columns)})
            mask = predicate(env)
            mask = jnp.asarray(mask, bool) & compact_mod.live_mask(cap, count)
            cols, m = keys_mod.compact_columns(mask, t.columns)
            return Table(cols, jnp.reshape(m, (1,)), names, ctx)

        # the predicate object itself keys the cache (kept alive by the cache
        # dict, so CPython id-reuse cannot alias two predicates)
        return _shard_wise(self.ctx, fn, self, key=("select", predicate))

    def merge(self, other: "Table") -> "Table":
        """Row concatenation (reference: table.cpp:278-299 Merge)."""
        _check_schemas(self, other)
        names, ctx = self.names, self.ctx

        def fn(a: Table, b: Table) -> Table:
            cap_a = a.columns[0].data.shape[0]
            cap_b = b.columns[0].data.shape[0]
            from .ops import common as common_mod
            mask = jnp.concatenate([compact_mod.live_mask(cap_a, a.row_counts[0]),
                                    compact_mod.live_mask(cap_b, b.row_counts[0])])
            cols, m = keys_mod.compact_columns(mask, [
                common_mod.concat_columns(ca, cb)
                for ca, cb in zip(a.columns, b.columns)])
            return Table(cols, jnp.reshape(m, (1,)), names, ctx)

        return _shard_wise(self.ctx, fn, self, other, key=("merge",))

    def sort(self, by, ascending: Union[bool, Sequence[bool]] = True,
             nulls_first: bool = True) -> "Table":
        """Shard-local sort (reference: local Sort, util::SortTable)."""
        by_idx = self._resolve_many(by)
        if isinstance(ascending, bool):
            asc = tuple([ascending] * len(by_idx))
        else:
            asc = tuple(ascending)
        names, ctx = self.names, self.ctx

        def fn(t: Table) -> Table:
            cols, count = sort_mod.sort_rows(t.columns, t.row_counts[0], by_idx, asc,
                                             nulls_first)
            return Table(cols, t.row_counts, names, ctx)

        with obs_span("table.sort", keys=len(by_idx)):
            return _shard_wise(self.ctx, fn, self,
                               key=("sort", by_idx, asc, nulls_first))

    # -- join ----------------------------------------------------------
    def join(self, other: "Table", config: Optional[JoinConfig] = None, *,
             on=None, left_on=None, right_on=None, how="inner",
             algorithm="sort") -> "Table":
        """Shard-local join (reference: join::joinTables via Table::Join,
        table.cpp:441-457). For distributed tables this joins shard-by-shard;
        use :meth:`distributed_join` for the shuffled global join.

        If the one-shot device program exceeds HBM (the join OUTPUT can
        dwarf resident inputs), single-shard tables fall back to the
        chunked out-of-core engine instead of dying
        (``CYLON_TPU_ONESHOT_FALLBACK=0`` disables)."""
        from . import resilience

        cfg = _join_config(self, other, config, on, left_on, right_on, how, algorithm)
        # capacity, not row_count: reading the live count would force a
        # device sync on every join just to label a span
        with obs_span("table.join", how=cfg.join_type.name,
                      algorithm=cfg.algorithm.name, capacity=self.capacity):
            try:
                resilience.fault_point("oneshot_join")
                return _local_join(self, other, cfg)
            except Exception as e:
                if not _oneshot_oom_fallback(self, other, e):
                    raise
                how_s = {JoinType.INNER: "inner", JoinType.LEFT: "left",
                         JoinType.RIGHT: "right",
                         JoinType.FULL_OUTER: "outer"}[cfg.join_type]
                algo_s = ("hash" if cfg.algorithm == JoinAlgorithm.HASH
                          else "sort")
                from . import exec as exec_mod

                res, _stats = exec_mod.chunked_join(
                    self, other, left_on=list(cfg.left_on),
                    right_on=list(cfg.right_on), how=how_s, algo=algo_s,
                    passes=_fallback_passes(), left_prefix=cfg.left_prefix,
                    right_prefix=cfg.right_prefix)
                expected = _join_output_names(self, other, cfg)
                return _table_from_fallback(res, expected, self.ctx)

    def distributed_join(self, other: "Table", config: Optional[JoinConfig] = None,
                         *, on=None, left_on=None, right_on=None, how="inner",
                         algorithm="sort") -> "Table":
        """Global join: shuffle both tables on key columns then join locally
        (reference: DistributedJoin, table.cpp:459-489).  An INNER join,
        and a LEFT join, whose left side is the one kept whole, split the
        exchange by skew (``parallel.ops.join_exchange``): left rows of a
        hot key stay on their shard and the right rows of that key go to
        every shard.  RIGHT and FULL_OUTER joins hash-exchange both sides:
        a right row on every shard would be emitted unmatched on each."""
        cfg = _join_config(self, other, config, on, left_on, right_on, how, algorithm)
        with obs_span("table.distributed_join", how=cfg.join_type.name,
                      algorithm=cfg.algorithm.name, world=self.num_shards):
            if self.num_shards == 1:
                return _local_join(self, other, cfg)
            from .parallel import ops as par_ops

            left_sh, right_sh, hot_keys = par_ops.join_exchange(
                self, other, cfg.left_on, cfg.right_on,
                split=cfg.join_type in (JoinType.INNER, JoinType.LEFT))
            out = _local_join(left_sh, right_sh, cfg)
            _stamp_join_partitioning(out, self, other, cfg, hot_keys)
            return out

    def plan(self) -> "LogicalPlan":
        """Start a lazy logical plan at this table (cylon_tpu.plan): a
        multi-op pipeline built this way runs through the rule-based
        optimizer — shuffle elision from tracked partitioning, column
        pruning before plane packing, fused post-shuffle local kernels
        — instead of one eager exchange per op.  ``execute()`` runs it,
        ``explain()`` shows every decision, and the durable journal /
        serve result cache fingerprint the whole plan as one unit."""
        from .plan import LogicalPlan

        return LogicalPlan.scan(self)

    # -- set ops -------------------------------------------------------
    def union(self, other: "Table") -> "Table":
        return _local_set_op(self, other, "union")

    def subtract(self, other: "Table") -> "Table":
        return _local_set_op(self, other, "subtract")

    def intersect(self, other: "Table") -> "Table":
        return _local_set_op(self, other, "intersect")

    def distributed_union(self, other: "Table") -> "Table":
        return _dist_set_op(self, other, "union")

    def distributed_subtract(self, other: "Table") -> "Table":
        return _dist_set_op(self, other, "subtract")

    def distributed_intersect(self, other: "Table") -> "Table":
        return _dist_set_op(self, other, "intersect")

    # -- unique --------------------------------------------------------
    def unique(self, columns=None, keep: str = "first") -> "Table":
        key_idx = (tuple(range(len(self.columns))) if columns is None
                   else self._resolve_many(columns))
        names, ctx = self.names, self.ctx

        def fn(t: Table) -> Table:
            cols, m = unique_mod.unique(t.columns, t.row_counts[0], key_idx, keep)
            return Table(cols, jnp.reshape(m, (1,)), names, ctx)

        with obs_span("table.unique", keys=len(key_idx)):
            return _shard_wise(self.ctx, fn, self,
                               key=("unique", key_idx, keep))

    def distributed_unique(self, columns=None, keep: str = "first") -> "Table":
        """reference: DistributedUnique (table.cpp:1031-1047): shuffle on the
        key columns, then local unique."""
        if self.num_shards == 1:
            return self.unique(columns, keep)
        key_idx = (tuple(range(len(self.columns))) if columns is None
                   else self._resolve_many(columns))
        from .parallel import ops as par_ops

        return par_ops.shuffle(self, key_idx).unique(key_idx, keep)

    # -- sort (global) -------------------------------------------------
    def distributed_sort(self, by, options: Optional[SortOptions] = None,
                         ascending: Union[bool, Sequence[bool], None] = None) -> "Table":
        """reference: DistributedSort (table.cpp:313-356): sampled-histogram
        range partition -> shuffle -> local sort."""
        opts = options or SortOptions()
        by_idx = self._resolve_many(by)
        if ascending is None:
            asc = tuple([opts.ascending] * len(by_idx))
        elif isinstance(ascending, bool):
            asc = tuple([ascending] * len(by_idx))
        else:
            asc = tuple(bool(a) for a in ascending)
            if len(asc) != len(by_idx):
                raise CylonError(Code.Invalid, "ascending length mismatch")
        if asc[0] != opts.ascending:
            opts = SortOptions(ascending=asc[0], num_bins=opts.num_bins,
                               num_samples=opts.num_samples,
                               nulls_first=opts.nulls_first)
        with obs_span("table.distributed_sort", keys=len(by_idx),
                      world=self.num_shards):
            if self.num_shards == 1:
                return self.sort(by, ascending=asc,
                                 nulls_first=opts.nulls_first)
            from .parallel import ops as par_ops

            return par_ops.distributed_sort(self, by_idx, opts, asc)

    # -- groupby -------------------------------------------------------
    def groupby(self, by, agg: Dict[ColumnRef, Union[str, Sequence[str]]],
                ddof: int = 0, groupby_type: str = "hash") -> "Table":
        """Group-by with two-phase distributed execution.

        ``groupby_type="hash"`` — the reference's DistributedHashGroupBy
        (groupby/groupby.cpp:23-73): local partial aggregate, shuffle on
        keys, final aggregate.  ``groupby_type="pipeline"`` —
        DistributedPipelineGroupBy (groupby/groupby.cpp:75-114): boundary-
        scan group-by over key-sorted rows (the caller guarantees each
        shard is sorted on the keys, as the reference does).  Local-only
        when the table has one shard."""
        if groupby_type not in ("hash", "pipeline"):
            raise CylonError(Code.Invalid,
                             f"bad groupby_type {groupby_type!r}")
        by_idx = self._resolve_many(by)
        aggs: List[Tuple[int, AggOp]] = []
        for ref, ops in agg.items():
            ci = self._resolve(ref)
            if isinstance(ops, (str, AggOp)):
                ops = [ops]
            for op in ops:
                aggs.append((ci, AggOp.of(op)))
        pipeline = groupby_type == "pipeline"
        with obs_span("table.groupby", kind=groupby_type, keys=len(by_idx),
                      aggs=len(aggs), world=self.num_shards):
            if self.num_shards == 1:
                from . import resilience

                try:
                    resilience.fault_point("oneshot_groupby")
                    return _local_groupby(self, by_idx, tuple(aggs), ddof,
                                          pipeline)
                except Exception as e:
                    # the chunked engine is hash-based: substituting it for
                    # a pipeline (run-length) group-by would silently merge
                    # non-adjacent key runs, so pipeline never falls back
                    if pipeline or not _oneshot_oom_fallback(self, None, e):
                        raise
                    from . import exec as exec_mod

                    agg_by_name: Dict[str, list] = {}
                    for ci, op in aggs:
                        agg_by_name.setdefault(self.names[ci], []).append(op)
                    res, _stats = exec_mod.chunked_groupby(
                        self, [self.names[i] for i in by_idx], agg_by_name,
                        ddof=ddof, passes=_fallback_passes())
                    expected = _groupby_output_names(self, by_idx,
                                                     tuple(aggs))
                    return _table_from_fallback(res, expected, self.ctx)
            from .parallel import ops as par_ops

            return par_ops.distributed_groupby(self, by_idx, tuple(aggs),
                                               ddof, pipeline)

    # -- scalar aggregates ---------------------------------------------
    def sum(self, ref: ColumnRef):
        return self._scalar_agg(ref, agg_mod.ReduceOp.SUM)

    def count(self, ref: ColumnRef):
        return self._scalar_agg(ref, agg_mod.ReduceOp.COUNT)

    def min(self, ref: ColumnRef):
        return self._scalar_agg(ref, agg_mod.ReduceOp.MIN)

    def max(self, ref: ColumnRef):
        return self._scalar_agg(ref, agg_mod.ReduceOp.MAX)

    def _scalar_agg(self, ref: ColumnRef, op: agg_mod.ReduceOp):
        """reference: compute::Sum/Count/Min/Max (compute/aggregates.cpp:
        30-156): local reduce + AllReduce over the mesh."""
        ci = self._resolve(ref)
        if self.num_shards == 1:
            v, _ = agg_mod.scalar_agg(self.columns[ci], self.row_counts[0], op)
            return v
        from .parallel import ops as par_ops

        return par_ops.distributed_scalar_agg(self, ci, op)

    # ------------------------------------------------------------------
    # element-wise compute surface (pycylon table.pyx:1026-1598 dunders,
    # 1599-2146 fillna/where/isnull/dropna/isin; data/compute.pyx kernels)
    # ------------------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, (str, int, np.integer)):
            return self.project([key])
        if isinstance(key, (list, tuple)):
            return self.project(list(key))
        if isinstance(key, Table):
            return self.filter(key)
        if isinstance(key, slice):
            return self._row_slice(key)
        raise CylonError(Code.Invalid, f"bad Table key {key!r}")

    def __setitem__(self, key: str, value) -> None:
        if not isinstance(key, str):
            raise CylonError(Code.Invalid, "column name must be a string")
        col = self._column_from_value(value)
        if key in self.names:
            i = self.names.index(key)
            self.columns = self.columns[:i] + (col,) + self.columns[i + 1:]
        else:
            self.columns = self.columns + (col,)
            self.names = self.names + (key,)

    def _column_from_value(self, value) -> Column:
        from . import compute as compute_mod

        if isinstance(value, Column):
            if value.capacity != self.capacity:
                raise CylonError(Code.Invalid, "column capacity mismatch")
            return value
        if isinstance(value, Table):
            if len(value.columns) != 1:
                raise CylonError(Code.Invalid, "expected a single-column table")
            return self._column_from_value(value.columns[0])
        if np.isscalar(value) or isinstance(value, (bool, int, float, str)):
            n = self.row_count
            return self._column_from_value(np.full((n,), value))
        arr = np.asarray(value)
        if arr.shape[0] != self.row_count:
            raise CylonError(Code.Invalid,
                             f"value length {arr.shape[0]} != rows {self.row_count}")
        if self.num_shards == 1:
            return column_mod.from_numpy(arr, capacity=self.capacity)
        counts = _host_row_counts(self)
        cap = self.shard_capacity
        off = 0
        shard_cols = []
        for s in range(self.num_shards):
            shard_cols.append(column_mod.from_numpy(
                arr[off: off + int(counts[s])], capacity=cap))
            off += int(counts[s])
        return _assemble_sharded(shard_cols, self.ctx)

    def _row_slice(self, sl: slice) -> "Table":
        if self.num_shards != 1:
            raise CylonError(Code.Invalid,
                             "row slicing requires a local (1-shard) table")
        start, stop, step = sl.indices(self.row_count)
        idx = jnp.arange(start, stop, step, dtype=jnp.int32)
        n = idx.shape[0]
        cap = max(8, n)
        pad_idx = jnp.concatenate(
            [idx, jnp.zeros((cap - n,), jnp.int32)]) if cap > n else idx
        from .ops import compact as compact_mod

        mask = compact_mod.live_mask(cap, jnp.asarray(n, jnp.int32))
        cols = tuple(c.take(pad_idx, valid_mask=mask) for c in self.columns)
        return Table(cols, jnp.asarray([n], jnp.int32), self.names, self.ctx)

    def filter(self, mask: "Table") -> "Table":
        """Row filter by a boolean table (pandas-style ``df[bool_mask]``;
        reference: table.pyx:991-1024 filter / c_filter compute.pyx:29-39)."""
        from .ops import compact as compact_mod

        if len(mask.columns) != 1:
            raise CylonError(Code.Invalid, "filter mask must have one column")
        if mask.columns[0].dtype.type != dtypes.Type.BOOL:
            raise CylonError(Code.Invalid, "filter mask must be boolean")
        names, ctx = self.names, self.ctx

        def fn(t: Table, m: Table) -> Table:
            cap = t.columns[0].data.shape[0]
            mc = m.columns[0]
            keep = mc.data & mc.validity & compact_mod.live_mask(cap, t.row_counts[0])
            cols, cnt = keys_mod.compact_columns(keep, t.columns)
            return Table(cols, jnp.reshape(cnt, (1,)), names, ctx)

        return _shard_wise(self.ctx, fn, self, mask, key=("filter",))

    # comparison dunders return boolean Tables (pycylon table.pyx:1170-1374)
    def __eq__(self, other):  # type: ignore[override]
        from . import compute as compute_mod

        return compute_mod.compare(self, other, "eq")

    def __ne__(self, other):  # type: ignore[override]
        from . import compute as compute_mod

        return compute_mod.compare(self, other, "ne")

    def __lt__(self, other):
        from . import compute as compute_mod

        return compute_mod.compare(self, other, "lt")

    def __gt__(self, other):
        from . import compute as compute_mod

        return compute_mod.compare(self, other, "gt")

    def __le__(self, other):
        from . import compute as compute_mod

        return compute_mod.compare(self, other, "le")

    def __ge__(self, other):
        from . import compute as compute_mod

        return compute_mod.compare(self, other, "ge")

    __hash__ = object.__hash__

    def __or__(self, other):
        from . import compute as compute_mod

        return compute_mod.logical_op(self, other, "or")

    def __and__(self, other):
        from . import compute as compute_mod

        return compute_mod.logical_op(self, other, "and")

    def __invert__(self):
        from . import compute as compute_mod

        return compute_mod.invert(self)

    def __neg__(self):
        from . import compute as compute_mod

        return compute_mod.neg(self)

    def __add__(self, other):
        from . import compute as compute_mod

        return compute_mod.add(self, other)

    def __sub__(self, other):
        from . import compute as compute_mod

        return compute_mod.subtract(self, other)

    def __mul__(self, other):
        from . import compute as compute_mod

        return compute_mod.multiply(self, other)

    def __truediv__(self, other):
        from . import compute as compute_mod

        return compute_mod.divide(self, other)

    def fillna(self, fill_value) -> "Table":
        from . import compute as compute_mod

        return compute_mod.fillna(self, fill_value)

    def where(self, condition, other=None) -> "Table":
        from . import compute as compute_mod

        return compute_mod.where(self, condition, other)

    def isnull(self) -> "Table":
        from . import compute as compute_mod

        return compute_mod.is_null(self)

    isna = isnull

    def notnull(self) -> "Table":
        from . import compute as compute_mod

        return compute_mod.invert(compute_mod.is_null(self))

    notna = notnull

    def dropna(self, axis: int = 0, how: str = "any") -> "Table":
        from . import compute as compute_mod

        return compute_mod.drop_na(self, how=how, axis=axis)

    def isin(self, values, skip_null: bool = True) -> "Table":
        from . import compute as compute_mod

        return compute_mod.is_in(self, values, skip_null)

    def drop(self, column_names) -> "Table":
        """Drop columns (reference: table.pyx:1625-1652)."""
        if isinstance(column_names, (str, int, np.integer)):
            column_names = [column_names]
        drop_idx = set(self._resolve_many(column_names))
        keep = [i for i in range(len(self.columns)) if i not in drop_idx]
        return self.project(keep)

    def applymap(self, fn) -> "Table":
        """Apply a vectorized function to every column's values
        (reference: python/test/test_udf applymap coverage)."""
        cols = []
        for c in self.columns:
            if c.is_string:
                raise CylonError(Code.Invalid, "applymap on string column")
            data = fn(c.data)
            cols.append(Column(jnp.where(c.validity, data,
                                         jnp.zeros((), data.dtype)),
                               c.validity, None,
                               dtypes.from_numpy_dtype(data.dtype)))
        return Table(tuple(cols), self.row_counts, self.names, self.ctx)

    # -- partitioning / shuffle ----------------------------------------
    def shuffle(self, refs) -> "Table":
        """Hash-repartition rows over the mesh (reference: Shuffle,
        table.cpp:951-964)."""
        if self.num_shards == 1:
            return self
        from .parallel import ops as par_ops

        with obs_span("table.shuffle", world=self.num_shards):
            return par_ops.shuffle(self, self._resolve_many(refs))

    def hash_partition(self, refs, num_partitions: int) -> Dict[int, "Table"]:
        """Split into ``num_partitions`` tables by key hash, shard-locally
        (reference: HashPartition, table.cpp:358-375)."""
        if num_partitions < 1:
            raise CylonError(Code.Invalid,
                             f"num_partitions must be >= 1, got {num_partitions}")
        from .parallel import ops as par_ops

        return par_ops.hash_partition(self, self._resolve_many(refs),
                                      num_partitions)


class _RowEnv:
    """Column namespace handed to select() predicates."""

    def __init__(self, cols: Dict[str, Column]):
        self._cols = cols

    def __getitem__(self, name: str) -> jax.Array:
        return self._cols[name].data

    def __getattr__(self, name: str) -> jax.Array:
        if name.startswith("_"):
            raise AttributeError(name)
        return self._cols[name].data

    def validity(self, name: str) -> jax.Array:
        return self._cols[name].validity


# ---------------------------------------------------------------------------
# internals
# ---------------------------------------------------------------------------



def _shard_wise(ctx: CylonContext, fn, *tables: Table, key: tuple,
                name: Optional[str] = None, operands: tuple = ()):
    """Run a per-shard table function: directly for 1-shard tables, under a
    cached jitted shard_map over the mesh otherwise.  This is how every
    'local' op of the reference (executed independently per MPI rank) maps
    onto the mesh.

    ``name`` makes the function a *stage program* (the planner's filter,
    derive, join-count and fused stages): one cached program of that name
    on one shard as on many, so a stage of eager primitives is compiled
    once and passes over its columns once.  An eager op's kernels are
    jitted programs already and run directly on one shard.  ``operands``
    are scalars the program takes after the tables (a predicate's
    literals), the same on every shard: their values are not in ``key``."""
    t0 = tables[0]
    world = t0.num_shards
    if world == 1 and name is None:
        return fn(*tables)
    from . import config

    # LRU-bounded: select predicates key entries by object identity, so an
    # unbounded dict would leak one compiled program per ad-hoc lambda.
    # Every trace-scope knob rides the key (trace_cache_token): the local-op
    # bodies trace accum/segsum/permute modes, and flipping one mid-process
    # must retrace, never serve the other realization (cylint CY103)
    cache = ctx_cache(ctx, "_shard_fn_cache", maxsize=256)
    cache_key = (key, world,
                 tuple(t.capacity for t in tables),
                 tuple(t.names for t in tables),
                 tuple(tuple((c.dtype, c.data.shape[1:]) for c in t.columns)
                       for t in tables),
                 config.trace_cache_token())
    entry = cache.get(cache_key)
    if entry is None:
        obs_metrics.counter_add("plan_cache.miss")
        if name is not None:
            fn.__name__ = fn.__qualname__ = name
        if world > 1:
            from jax.sharding import PartitionSpec as P

            from .utils import shard_map

            spec = P(PARTITION_AXIS)
            fn = shard_map(fn, mesh=ctx.mesh,
                           in_specs=(spec,) * len(tables)
                           + (P(),) * len(operands),
                           out_specs=spec, check_vma=False)
        entry = cache[cache_key] = jax.jit(fn)
    else:
        obs_metrics.counter_add("plan_cache.hit")
    return entry(*tables, *operands)


# a shard's live rows leave the device in slices of a multiple of
# capacity / _FETCH_STEPS rows: at most that many slice programs a buffer
# shape, and under 1% of the capacity moved for nothing
_FETCH_STEPS = 128


def _live_shard_rows(buffers: Sequence[jax.Array], counts: np.ndarray,
                     cap: int):
    """For each of ``buffers`` (global arrays of ``cap`` rows a shard) in
    turn: {shard_id: host ndarray of that shard's ``counts[shard_id]`` live
    rows}, from the array's process-addressable device buffers only (no
    cross-process transfer).  The one piece of code that reads shards.

    A shard's rows are sliced on the device that holds them, to the count
    rounded up to a step (a new count seldom compiles a new slice program;
    the surplus is cut off here), and every copy of every buffer is started
    before the first is waited for: each chip has its own link to the
    host.  An empty shard moves nothing; a replicated buffer spans every
    shard and is sliced accordingly.  The dispatch and each buffer's wait
    are spans ``table.fetch.d2h``, and a 64-bit buffer's wait is also
    ``table.fetch.d2h.wide`` inside it (``column.wide_wait``): the buffers
    are waited for in flatten order, so that span is the host's wait on a
    64-bit buffer once the earlier ones have landed, not its transfer
    time.  The bytes that arrived add to counter ``table.fetch.bytes``."""
    step = max(1, cap // _FETCH_STEPS)
    in_flight = collections.deque()
    with obs_span("table.fetch.d2h"):
        for arr in buffers:
            slices: Dict[int, Optional[jax.Array]] = {}
            for sh in arr.addressable_shards:
                idx = sh.index[0] if sh.index else slice(None)
                first = (0 if idx.start is None else int(idx.start)) // cap
                for k in range(sh.data.shape[0] // cap):
                    sid = first + k
                    if sid in slices:
                        continue
                    slices[sid] = None
                    n = int(counts[sid])
                    if n:
                        rows = min(cap, -(-n // step) * step)
                        slices[sid] = sh.data[k * cap: k * cap + rows]
                        slices[sid].copy_to_host_async()
            in_flight.append((arr, slices))

    def landed():
        # a buffer's device slices are let go as soon as it has landed
        while in_flight:
            arr, slices = in_flight.popleft()
            with obs_span("table.fetch.d2h"), column_mod.wide_wait(arr):
                rows = {sid: (np.empty((0,) + arr.shape[1:], arr.dtype)
                              if piece is None else np.asarray(piece))
                        for sid, piece in slices.items()}
                obs_metrics.counter_add(
                    "table.fetch.bytes", sum(r.nbytes for r in rows.values()))
            yield {sid: r[:int(counts[sid])] for sid, r in rows.items()}

    return landed()


def _get_everywhere(tree):
    """``tree`` on the host, valid on every process: a cross-process
    all-gather where the shards live on several, else a device_get."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(tree, tiled=True)
    return jax.device_get(tree)


def host_sync(value, why: str, get=jax.device_get):
    """The one door through which the main path reads a device value to
    choose its next program (a row count, a capacity, an exchange plan):
    span ``host.sync`` and counter ``host.syncs``; returns the value on
    the host.  The reads of a fetch are its own (``table.fetch.d2h``),
    not these."""
    with obs_span("host.sync", why=why):
        obs_metrics.counter_add("host.syncs")
        return get(value)


def _host_row_counts(t: Table, why: str = "row_counts") -> np.ndarray:
    """Per-shard row counts as a host array, valid on every process."""
    return np.asarray(host_sync(t.row_counts, why, _get_everywhere))


class _TableIndexer:
    """loc/iloc row access, one implementation parameterized by kind
    (loc: the WORKING analog of the reference's stubbed _libs/index.pyx
    LocIndexr.get_loc; iloc: pandas positional semantics)."""

    def __init__(self, table: Table, kind: str):
        self._t = table
        self._kind = kind

    def __getitem__(self, key) -> Table:
        from .index import iloc_positions, loc_positions

        key, cols = _split_row_col_key(key, self._t.names,
                                       split_always=self._kind == "iloc")
        try:
            if self._kind == "loc":
                pos = loc_positions(self._t.index, key, self._t.row_count)
            else:
                pos = iloc_positions(key, self._t.row_count)
        except KeyError as e:
            raise CylonError(Code.KeyError, str(e))
        except IndexError as e:
            raise CylonError(Code.IndexError, str(e))
        out = self._t.take_rows(pos)
        if cols is not None:
            sub = out.project(cols)
            sub._index = out._index  # project builds a fresh Table
            out = sub
        return out


def _split_row_col_key(key, names, split_always: bool = False):
    """``indexer[rows, cols]`` support: a 2-tuple whose second element
    selects columns.  For iloc (``split_always``) a 2-tuple is ALWAYS
    (rows, cols) — iloc has no tuple labels, and pandas' ``iloc[0, 1]``
    means cell access, never rows (0, 1).  For loc a tuple is also how
    multi-index labels spell, so the second element only counts as a
    column selection when it actually names table columns (or is a
    positional int with non-scalar rows)."""
    if isinstance(key, tuple) and len(key) == 2:
        rows, cols = key
        if split_always:
            if isinstance(cols, (int, np.integer, str)):
                return rows, [cols if isinstance(cols, str) else int(cols)]
            if isinstance(cols, slice):
                return rows, list(names[cols])
            return rows, cols  # lists pass through; project() validates
        if isinstance(cols, str) and cols in names:
            return rows, [cols]
        if isinstance(cols, list) and cols and \
                all(isinstance(c, str) and c in names for c in cols):
            return rows, cols
        if isinstance(cols, (int, np.integer)) and \
                not isinstance(rows, (int, np.integer, str)):
            return rows, [int(cols)]
    return key, None


def _check_schemas(a: Table, b: Table) -> None:
    if len(a.columns) != len(b.columns):
        raise CylonError(Code.Invalid, "column count mismatch")
    for (na, ca), (nb, cb) in zip(a.schema, b.schema):
        if ca.type != cb.type:
            raise CylonError(Code.Invalid,
                             f"schema mismatch: {na}:{ca} vs {nb}:{cb}")


def _join_config(left: Table, right: Table, config, on, left_on, right_on,
                 how, algorithm) -> JoinConfig:
    if config is not None:
        cfg = config
        left_idx = left._resolve_many(cfg.left_on)
        right_idx = right._resolve_many(cfg.right_on)
        return _check_join_keys(left, right,
                                JoinConfig(cfg.join_type, cfg.algorithm, left_idx,
                                           right_idx, cfg.left_prefix,
                                           cfg.right_prefix))
    if on is not None:
        left_on = right_on = on
    if left_on is None or right_on is None:
        raise CylonError(Code.Invalid, "join requires on= or left_on=/right_on=")
    cfg = JoinConfig.of(how, algorithm, left_on, right_on)
    cfg = JoinConfig(cfg.join_type, cfg.algorithm,
                     left._resolve_many(cfg.left_on),
                     right._resolve_many(cfg.right_on),
                     cfg.left_prefix, cfg.right_prefix)
    return _check_join_keys(left, right, cfg)


def _check_join_keys(left: Table, right: Table, cfg: JoinConfig) -> JoinConfig:
    if len(cfg.left_on) != len(cfg.right_on):
        raise CylonError(Code.Invalid, "left_on/right_on length mismatch")
    for li, ri in zip(cfg.left_on, cfg.right_on):
        lt, rt = left.columns[li].dtype, right.columns[ri].dtype
        # string keys only need to agree on string-likeness (widths are
        # padded to match); everything else must match EXACTLY —
        # concatenating an int64 key column with an int32 one silently
        # promotes and mis-orders the packed sort operands (verified to
        # corrupt join output).  The reference's typed comparators reject
        # this at kernel dispatch (arrow_comparator.hpp); we reject at
        # the API.
        kind = dtypes.join_key_mismatch(
            dtypes.is_string_like(lt), dtypes.is_string_like(rt), lt == rt,
            # row_count is only consulted on the rare mismatch path — the
            # host sync it costs never lands on a well-typed join
            lt != rt and (left.row_count == 0 or right.row_count == 0))
        if kind is not None:
            raise CylonError(
                Code.Invalid,
                f"join key type mismatch: {left.names[li]}:{lt} vs "
                f"{right.names[ri]}:{rt} (cast the keys to a common type)")
    return cfg


def _stamp_join_partitioning(out: Table, left: Table, right: Table,
                             cfg: JoinConfig, hot_keys: int = 0) -> None:
    """Record the shuffled join's output partitioning as a tracked
    property (the planner's shuffle-elision substrate).  Which side's
    key names survive as valid hash alternatives — INNER both, LEFT
    left keys, RIGHT right keys, FULL_OUTER neither — is the planner's
    single-sourced rule, shared so the eager stamp and the optimizer's
    derived property can never disagree.  A join whose exchange kept
    ``hot_keys`` > 0 keys' rows in place is not partitioned by its keys:
    it carries no stamp."""
    from .plan.optimizer import join_partition_alternatives

    if hot_keys:
        return
    how = {JoinType.INNER: "inner", JoinType.LEFT: "left",
           JoinType.RIGHT: "right", JoinType.FULL_OUTER: "outer"}[
        cfg.join_type]
    alts = join_partition_alternatives(
        how, left.names, right.names,
        [left.names[i] for i in cfg.left_on],
        [right.names[i] for i in cfg.right_on],
        cfg.left_prefix, cfg.right_prefix)
    if alts:
        out._partitioning = ("hash", alts, left.num_shards)


def _join_output_names(left: Table, right: Table, cfg: JoinConfig) -> Tuple[str, ...]:
    """left names ++ right names, prefixing collisions (reference:
    join_utils.cpp build_final_table column naming)."""
    lnames = list(left.names)
    rnames = list(right.names)
    collisions = set(lnames) & set(rnames)
    out_l = [cfg.left_prefix + n if n in collisions else n for n in lnames]
    out_r = [cfg.right_prefix + n if n in collisions else n for n in rnames]
    return tuple(out_l + out_r)


def _cap_round(n: int) -> int:
    """Round a dynamic row count up to a 3-bit-mantissa capacity (at most 8
    distinct sizes per octave): tight enough that a count just past a power
    of two doesn't double every downstream kernel, coarse enough that the
    jit cache stays warm."""
    if n <= 16:
        return 16
    g = 1 << ((n - 1).bit_length() - 3)
    return -(-n // g) * g


def _oneshot_oom_fallback(left: Table, right: Optional[Table],
                          exc: Exception) -> bool:
    """True when a failed one-shot device op should fall back to the
    chunked out-of-core engine: the failure classifies as OutOfMemory
    (real RESOURCE_EXHAUSTED or injected), every involved table is
    single-shard (distributed recovery is the mesh's job), and the knob
    (``CYLON_TPU_ONESHOT_FALLBACK``, default on) allows it."""
    from . import config
    from .status import Status

    if Status.from_exception(exc).code != Code.OutOfMemory:
        return False
    if not config.knob("CYLON_TPU_ONESHOT_FALLBACK"):
        return False
    if left.num_shards != 1 or (right is not None and right.num_shards != 1):
        return False
    import logging

    from . import durable
    from .obs import instant as obs_instant

    # the fallback run rides the chunked engine, so with a durable dir
    # set it is journaled and crash-resumable — record which, so a trace
    # shows whether a later kill would lose the recovery work
    obs_instant("table.oneshot_fallback", durable=durable.enabled())
    logging.getLogger(__name__).warning(
        "one-shot device program exceeded memory (%s); falling back to the "
        "chunked out-of-core engine%s", type(exc).__name__,
        " (journaled: CYLON_TPU_DURABLE_DIR set)" if durable.enabled()
        else "")
    return True


def _fallback_passes() -> int:
    """Initial pass count for the one-shot -> chunked fallback
    (``CYLON_TPU_FALLBACK_PASSES``, default 4); the chunked engine's own
    OOM recovery refines further if even that is too coarse."""
    from . import config

    return max(2, int(config.knob("CYLON_TPU_FALLBACK_PASSES")))


def _table_from_fallback(res: Dict[str, np.ndarray], expected, ctx) -> Table:
    """Host-column dict from the chunked engine -> Table, reordered to the
    one-shot op's output schema when the names agree."""
    if set(res) == set(expected):
        res = {n: res[n] for n in expected}
    return Table.from_numpy(list(res), list(res.values()), ctx=ctx)


def _local_join(left: Table, right: Table, cfg: JoinConfig) -> Table:
    """Local join with adaptive output sizing.

    The reference reserves exactly via a dedicated count pass every call
    (join/join_utils.cpp); on TPU the count pass re-runs the whole match
    kernel, so steady state reuses the last adequate capacity for this
    (join, shapes) site and runs ONE gather — falling back to the exact
    two-pass (count -> gather) only on the first call or when the cached
    capacity proves too small (the gather's returned row count is checked
    against it before the result is used)."""
    names = _join_output_names(left, right, cfg)
    ctx = left.ctx
    jt = cfg.join_type

    algo = "hash" if cfg.algorithm == JoinAlgorithm.HASH else "sort"
    cap_cache = ctx_cache(ctx, "_join_cap_cache")
    site = ("join_cap", cfg.left_on, cfg.right_on, jt, algo,
            left.shard_capacity, right.shard_capacity,
            tuple(c.dtype for c in left.columns),
            tuple(c.dtype for c in right.columns))

    def gather_at(out_cap: int, counts=None):
        """(the gather at ``out_cap`` slots a shard, the fullest shard's
        rows).  ``counts``: the join's rows a shard where the count pass
        has brought them to the host, else read off the result, which is
        the check of a remembered capacity."""
        def gather_fn(a: Table, b: Table) -> Table:
            cols, m = join_mod.join_gather(
                a.columns, a.row_counts[0], b.columns, b.row_counts[0],
                cfg.left_on, cfg.right_on, jt, out_cap, algo)
            return Table(cols, jnp.reshape(m, (1,)), names, ctx)

        # 32-bit lanes through the join's slot -> row indices (take_side)
        obs_metrics.counter_add("join.take_lanes", sum(
            join_mod.take_lanes(t.columns) for t in (left, right)))
        slots = out_cap * left.num_shards
        with obs_span("join.gather", slots=slots) as sp:
            out = _shard_wise(ctx, gather_fn, left, right,
                              key=("join", cfg.left_on, cfg.right_on, jt,
                                   out_cap, algo))
            if counts is None:
                counts = _host_row_counts(out, "join.capacity")
            rows = int(np.sum(counts))
            sp.set(rows=rows)
        hi = int(np.max(counts))
        if hi <= out_cap:
            # a join that ran, and how full its slots are: the fullest
            # shard sizes them for all, and every later buffer with them
            obs_metrics.counter_add("join.out_rows", rows)
            obs_metrics.counter_add("join.out_slots", slots)
            obs_metrics.counter_add("join.shard_rows_max", hi)
        return out, hi

    cached = cap_cache.get(site)
    if cached is not None:
        out, hi = gather_at(cached)
        if hi <= cached:
            # shrink with hysteresis: one skewed join must not inflate
            # this site (and everything sized off its result) forever
            need = _cap_round(max(1, hi))
            if need * 4 <= cached:
                cap_cache[site] = need * 2
            return out
        # cached capacity too small: the gather truncated; fall through to
        # the exact two-pass and remember the larger size

    def count_fn(a: Table, b: Table):
        c = join_mod.join_row_count(a.columns, a.row_counts[0], b.columns,
                                    b.row_counts[0], cfg.left_on, cfg.right_on,
                                    jt, algo)
        return jnp.reshape(c, (1,))

    # sizing pass + gather pass, the 2-pass Reserve/build of the reference's
    # join builder (join/join_utils.cpp), with chrono-span parity
    # (join.cpp:89-253 phase timers)
    with obs_span("join.count"):
        counts = _shard_wise(ctx, count_fn, left, right,
                             key=("join_count", cfg.left_on, cfg.right_on, jt,
                                  algo))
        counts = np.asarray(host_sync(counts, "join.count", _get_everywhere))
        out_cap = _cap_round(max(1, int(np.max(counts))))
    cap_cache[site] = out_cap
    return gather_at(out_cap, counts)[0]


def _local_set_op(a: Table, b: Table, op: str) -> Table:
    _check_schemas(a, b)
    names, ctx = a.names, a.ctx
    out_cap = _pow2ceil(a.shard_capacity + b.shard_capacity)

    def fn(ta: Table, tb: Table) -> Table:
        cols, m = setops_mod.set_op(ta.columns, ta.row_counts[0],
                                    tb.columns, tb.row_counts[0], op, out_cap)
        return Table(cols, jnp.reshape(m, (1,)), names, ctx)

    return _shard_wise(ctx, fn, a, b, key=("setop", op, out_cap))


def _dist_set_op(a: Table, b: Table, op: str) -> Table:
    """reference: DoDistributedSetOperation (table.cpp:740-801): shuffle both
    tables on ALL columns, then the local set op."""
    if a.num_shards == 1:
        return _local_set_op(a, b, op)
    from .parallel import ops as par_ops

    all_cols = tuple(range(len(a.columns)))
    return _local_set_op(par_ops.shuffle(a, all_cols),
                         par_ops.shuffle(b, all_cols), op)


def _local_groupby(t: Table, by_idx: Tuple[int, ...],
                   aggs: Tuple[Tuple[int, AggOp], ...], ddof: int,
                   pipeline: bool = False) -> Table:
    names = _groupby_output_names(t, by_idx, aggs)
    ctx = t.ctx
    local = (groupby_mod.pipeline_groupby if pipeline
             else groupby_mod.hash_groupby)

    def fn(tt: Table) -> Table:
        cols, m = local(tt.columns, tt.row_counts[0], by_idx, aggs, ddof)
        return Table(cols, jnp.reshape(m, (1,)), names, ctx)

    return _shard_wise(ctx, fn, t, key=("groupby", by_idx, aggs, ddof, pipeline))


def _groupby_output_names(t: Table, by_idx, aggs) -> Tuple[str, ...]:
    names = [t.names[i] for i in by_idx]
    for ci, op in aggs:
        names.append(f"{op.name.lower()}_{t.names[ci]}")
    return tuple(names)


# ---------------------------------------------------------------------------
# host construction helpers
# ---------------------------------------------------------------------------

def _table_from_numpy(arrays: Dict[str, np.ndarray], ctx: CylonContext,
                      capacity: Optional[int]) -> Table:
    names = tuple(arrays.keys())
    n = len(next(iter(arrays.values()))) if arrays else 0
    for k, v in arrays.items():
        if len(v) != n:
            raise CylonError(Code.Invalid, f"column {k} length {len(v)} != {n}")
    world = ctx.GetWorldSize()
    if world == 1:
        cap = capacity or max(8, n)
        cols = tuple(column_mod.from_numpy(v, capacity=cap) for v in arrays.values())
        return Table(cols, jnp.asarray([n], jnp.int32), names, ctx)
    return _distribute_numpy(arrays, names, n, ctx, capacity)


def _table_from_arrow(arrays: Dict[str, object], ctx: CylonContext,
                      capacity: Optional[int],
                      string_width: Optional[int] = None) -> Table:
    import pyarrow as pa

    from .column import DEFAULT_STRING_WIDTH

    sw = string_width or DEFAULT_STRING_WIDTH
    names = tuple(arrays.keys())
    vals = []
    for a in arrays.values():
        if isinstance(a, pa.ChunkedArray):
            a = a.combine_chunks()
        vals.append(a)
    n = len(vals[0]) if vals else 0
    world = ctx.GetWorldSize()
    if world == 1:
        cap = capacity or max(8, n)
        cols = tuple(column_mod.from_arrow(a, capacity=cap, string_width=sw)
                     for a in vals)
        return Table(cols, jnp.asarray([n], jnp.int32), names, ctx)
    chunk, counts, shard_cap = _shard_plan(n, world, capacity)
    cols = []
    for a in vals:
        shard_cols = [column_mod.from_arrow(a.slice(s * chunk, counts[s]),
                                            capacity=shard_cap, string_width=sw)
                      for s in range(world)]
        cols.append(_assemble_sharded(shard_cols, ctx))
    return Table(tuple(cols), _sharded_counts(counts, ctx), names, ctx)


def _table_from_arrow_tables(atables, ctx: CylonContext,
                             capacity: Optional[int], *, per_shard: bool,
                             string_width: Optional[int] = None) -> Table:
    """Build a Table from host Arrow tables.

    per_shard=True: table i becomes mesh shard i (the reference's
    one-file-per-rank FromCSV semantics, table.cpp:810-855); requires
    ``len(atables) == world``.  per_shard=False: a single table whose rows
    are split contiguously across shards.
    """
    import pyarrow as pa

    from .column import DEFAULT_STRING_WIDTH

    sw = string_width or DEFAULT_STRING_WIDTH
    if not atables:
        raise CylonError(Code.Invalid, "no input files")
    names = tuple(atables[0].column_names)
    schema0 = atables[0].schema
    for i, at in enumerate(atables[1:], 1):
        if tuple(at.column_names) != names:
            raise CylonError(Code.Invalid,
                             f"schema mismatch across files: {at.column_names} "
                             f"vs {list(names)}")
        if at.schema != schema0:
            # unify inferred types (int64 in one file, double in another)
            # rather than corrupting buffers downstream
            try:
                import pyarrow as pa

                unified = pa.unify_schemas([schema0, at.schema],
                                           promote_options="permissive")
                atables = [t.cast(unified) for t in atables]
                schema0 = unified
            except Exception as e:
                raise CylonError(
                    Code.Invalid,
                    f"column type mismatch between file 0 and file {i}: "
                    f"{schema0} vs {at.schema}") from e
    world = ctx.GetWorldSize()
    if not per_shard or world == 1:
        combined = pa.concat_tables(atables) if len(atables) > 1 else atables[0]
        arrays = {n: combined.column(n) for n in names}
        return _table_from_arrow(arrays, ctx, capacity, string_width=sw)
    if len(atables) != world:
        raise CylonError(Code.Invalid,
                         f"{len(atables)} files for a {world}-shard mesh; "
                         "per-shard reads need one file per mesh position")
    counts = [at.num_rows for at in atables]
    shard_cap = capacity // world if capacity else max(8, max(counts))
    if shard_cap < max(counts):
        big = counts.index(max(counts))
        raise CylonError(
            Code.Invalid,
            f"capacity {capacity} gives {shard_cap} rows per shard but file "
            f"{big} has {counts[big]} rows")
    cols = []
    for name in names:
        shard_cols = [column_mod.from_arrow(at.column(name), capacity=shard_cap,
                                            string_width=sw)
                      for at in atables]
        cols.append(_assemble_sharded(shard_cols, ctx))
    return Table(tuple(cols), _sharded_counts(counts, ctx), names, ctx)


def _table_from_native_tables(ntables, ctx: CylonContext,
                              capacity: Optional[int], *, per_shard: bool,
                              string_width: Optional[int] = None) -> Table:
    """Build a Table from the native CSV reader's (names, cols) outputs —
    the native-ingest mirror of ``_table_from_arrow_tables``.  Each element
    of ``ntables`` is ``(names, cols)`` with cols holding ``data`` /
    ``validity`` / optional ``lengths`` numpy buffers (cylon_tpu/native)."""
    if not ntables:
        raise CylonError(Code.Invalid, "no input files")
    names = tuple(ntables[0][0])
    ncols = len(names)
    for i, (nm, _) in enumerate(ntables[1:], 1):
        if tuple(nm) != names:
            raise CylonError(Code.Invalid,
                             f"schema mismatch across files: {nm} vs "
                             f"{list(names)}")
    # unify numeric dtypes across files (int64 in one, float64 in another)
    for c in range(ncols):
        kinds = {nt[1][c]["data"].dtype.kind if nt[1][c]["data"].ndim == 1
                 else "S" for nt in ntables}
        if "S" in kinds and kinds != {"S"}:
            raise CylonError(Code.Invalid,
                             f"column {names[c]} is string in some files, "
                             "numeric in others")
        if "f" in kinds and "i" in kinds:
            for nt in ntables:
                nt[1][c]["data"] = nt[1][c]["data"].astype(np.float64)
    world = ctx.GetWorldSize()
    if not per_shard or world == 1:
        if len(ntables) == 1:
            nm, cols = ntables[0]
        else:
            nm = names
            cols = []
            for c in range(ncols):
                parts = [nt[1][c] for nt in ntables]
                merged: Dict[str, np.ndarray] = {}
                if parts[0]["data"].ndim == 2:
                    w = max(p["data"].shape[1] for p in parts)
                    mats = []
                    for p in parts:
                        m = p["data"]
                        if m.shape[1] < w:
                            m = np.pad(m, ((0, 0), (0, w - m.shape[1])))
                        mats.append(m)
                    merged["data"] = np.concatenate(mats)
                    merged["lengths"] = np.concatenate(
                        [p["lengths"] for p in parts])
                else:
                    merged["data"] = np.concatenate([p["data"] for p in parts])
                merged["validity"] = np.concatenate(
                    [p["validity"] for p in parts])
                cols.append(merged)
        n = len(cols[0]["data"]) if cols else 0
        if world == 1:
            cap = capacity or max(8, n)
            built = tuple(
                column_mod.from_native_buffers(
                    c["data"], c.get("validity"), c.get("lengths"),
                    capacity=cap, string_width=string_width)
                for c in cols)
            return Table(built, jnp.asarray([n], jnp.int32), names, ctx)
        chunk, counts, shard_cap = _shard_plan(n, world, capacity)
        out_cols = []
        for c in cols:
            shard_cols = [
                column_mod.from_native_buffers(
                    c["data"][s * chunk: s * chunk + counts[s]],
                    c["validity"][s * chunk: s * chunk + counts[s]],
                    None if "lengths" not in c
                    else c["lengths"][s * chunk: s * chunk + counts[s]],
                    capacity=shard_cap, string_width=string_width)
                for s in range(world)]
            out_cols.append(_assemble_sharded(shard_cols, ctx))
        return Table(tuple(out_cols), _sharded_counts(counts, ctx), names, ctx)
    if len(ntables) != world:
        raise CylonError(Code.Invalid,
                         f"{len(ntables)} files for a {world}-shard mesh; "
                         "per-shard reads need one file per mesh position")
    counts = [len(nt[1][0]["data"]) if nt[1] else 0 for nt in ntables]
    shard_cap = capacity // world if capacity else max(8, max(counts))
    if shard_cap < max(counts):
        big = counts.index(max(counts))
        raise CylonError(
            Code.Invalid,
            f"capacity {capacity} gives {shard_cap} rows per shard but file "
            f"{big} has {counts[big]} rows")
    out_cols = []
    for c in range(ncols):
        shard_cols = [
            column_mod.from_native_buffers(
                nt[1][c]["data"], nt[1][c].get("validity"),
                nt[1][c].get("lengths"), capacity=shard_cap,
                string_width=string_width)
            for nt in ntables]
        out_cols.append(_assemble_sharded(shard_cols, ctx))
    return Table(tuple(out_cols), _sharded_counts(counts, ctx), names, ctx)


def _distribute_numpy(arrays: Dict[str, np.ndarray], names, n: int,
                      ctx: CylonContext, capacity: Optional[int]) -> Table:
    """Split rows into contiguous per-shard chunks and lay them out as one
    global sharded array per buffer (shard i <-> mesh position i)."""
    world = ctx.GetWorldSize()
    chunk, counts, shard_cap = _shard_plan(n, world, capacity)
    cols = []
    for v in arrays.values():
        shard_cols = [column_mod.from_numpy(v[s * chunk: s * chunk + counts[s]],
                                            capacity=shard_cap)
                      for s in range(world)]
        cols.append(_assemble_sharded(shard_cols, ctx))
    return Table(tuple(cols), _sharded_counts(counts, ctx), names, ctx)


def _shard_plan(n: int, world: int, capacity: Optional[int]):
    chunk = math.ceil(n / world) if n else 0
    counts = [max(0, min(chunk, n - s * chunk)) for s in range(world)]
    shard_cap = capacity // world if capacity else max(8, chunk)
    return chunk, counts, shard_cap


def _sharded_counts(counts, ctx: CylonContext) -> jax.Array:
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(np.asarray(counts, np.int32),
                          NamedSharding(ctx.mesh, P(PARTITION_AXIS)))


def _assemble_sharded(shard_cols: List[Column], ctx: CylonContext) -> Column:
    """Stack per-shard Columns (validity and all) into one global column
    sharded over the mesh, padding string widths to a common value."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(ctx.mesh, P(PARTITION_AXIS))
    if shard_cols[0].is_string:
        w = max(c.string_width for c in shard_cols)
        padded = []
        for c in shard_cols:
            if c.string_width < w:
                extra = jnp.zeros((c.data.shape[0], w - c.string_width), jnp.uint8)
                c = Column(jnp.concatenate([c.data, extra], axis=1),
                           c.validity, c.lengths, c.dtype)
            padded.append(c)
        shard_cols = padded
    data = jax.device_put(
        np.concatenate([np.asarray(c.data) for c in shard_cols]), sharding)
    validity = jax.device_put(
        np.concatenate([np.asarray(c.validity) for c in shard_cols]), sharding)
    lengths = None
    if shard_cols[0].lengths is not None:
        lengths = jax.device_put(
            np.concatenate([np.asarray(c.lengths) for c in shard_cols]), sharding)
    return Column(data, validity, lengths, shard_cols[0].dtype)
