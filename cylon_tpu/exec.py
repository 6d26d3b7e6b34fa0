"""Out-of-core execution: key-partitioned streaming passes for inputs
larger than one chip's (or one mesh's) HBM.

The reference scales past one node by adding MPI ranks
(docs/docs/arch.md:146-162 — each rank holds a partition, the shuffle
moves rows); the TPU analog is to split the KEY DOMAIN into P disjoint
parts and stream one part at a time through the same compiled program:

- every pass reuses ONE static-shape XLA program (chunk capacities are
  maxed over passes, so nothing recompiles);
- because parts partition the key domain, a join pass only needs that
  part's rows from BOTH sides — every join type is exact per pass;
- a group-by whose keys pin down the partitioning key is FINAL per pass
  (host concatenation replaces any cross-pass combine); otherwise each
  pass emits PARTIAL aggregate states (the same SUM/COUNT/SUMSQ
  decomposition the distributed two-phase group-by shuffles,
  reference groupby/groupby.cpp:23-73) and one small device group-by
  combines them at the end;
- the host holds the full inputs (numpy); each pass uploads ~1/P of the
  data, so device residency is bounded by the pass size, not the input.

Two partitioners cover the key-type surface (both host-side, numpy):
``range`` splits on sample quantiles of an order-preserving uint64
prefix of the first key column (ints/floats exactly; strings by their
first eight codepoints, one clamped byte each — collisions only affect
balance, never correctness, because equal keys always share a prefix);
``hash`` mixes every key column's FULL content through a splitmix64
finalizer, which is skew-proof for distinct keys.  ``auto`` starts with
``range`` and flips to ``hash`` when the planned passes come out
pathologically unbalanced or fan out less than the distinct keys allow.

This is the 1B-row ladder of BASELINE.md: the single-chip rung runs the
fused kernel pipeline per pass; handing a distributed context shards
every pass over the mesh with the public distributed operators instead.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import column as colmod
from . import durable
from . import resilience
from . import config
from .obs import fleet as obs_fleet
from .obs import metrics as obs_metrics
from .obs import spans as obs_spans
from .config import JoinConfig, JoinType
from .ops import groupby as groupby_mod
from .ops import join as join_mod
from .ops.groupby import AggOp
from .status import Code, CylonError, Status
from .utils import pow2ceil


# ---------------------------------------------------------------------------
# host frames
# ---------------------------------------------------------------------------

def _as_host_frame(obj) -> Tuple[List[str], Dict[str, np.ndarray]]:
    """Normalize a pandas DataFrame / dict-of-arrays / Table to
    (ordered names, dict of host numpy columns)."""
    if isinstance(obj, dict):
        # stringify KEYS AND NAMES together — a names list of raw int
        # keys against a str-keyed dict would crash every lookup
        return ([str(k) for k in obj],
                {str(k): np.asarray(v) for k, v in obj.items()})
    if hasattr(obj, "columns") and hasattr(obj, "to_numpy") \
            and hasattr(obj, "names"):          # cylon_tpu Table
        return list(obj.names), obj.to_numpy()
    try:
        import pandas as pd
    except ImportError:
        # only a MISSING pandas disables DataFrame support; a broken
        # install must surface, not silently reject every DataFrame
        pd = None
    if pd is not None and isinstance(obj, pd.DataFrame):
        return ([str(c) for c in obj.columns],
                {str(c): obj[c].to_numpy() for c in obj.columns})
    raise CylonError(Code.Invalid,
                     f"expected DataFrame/dict/Table, got {type(obj)}")


#: public name (PR 19): the streaming layer's ``StreamTable.append``
#: accepts exactly the inputs the chunked engine does, through the same
#: normalizer — the two can never disagree on what a "frame" is
as_host_frame = _as_host_frame


_U63 = np.uint64(1) << np.uint64(63)


def _key_prefix_u64(a: np.ndarray) -> np.ndarray:
    """Order-preserving uint64 planning prefix: equal keys ALWAYS map to
    equal prefixes (the partition-correctness invariant); distinct keys
    may collide (strings beyond eight codepoints), which only affects
    pass balance.  Nulls/NaNs collapse to one prefix each, matching the
    device kernels' null-equality grouping."""
    a = np.asarray(a)
    if a.dtype.kind in ("U", "S", "O"):
        cp = _codepoints(a, 8)       # str() coercion: None -> "None", fine
        if cp is None:
            return np.zeros(0, np.uint64)
        # one byte per leading codepoint (clamped at 255: clamping can
        # only merge prefixes, never split equal keys)
        b = np.minimum(cp, 255).astype(np.uint64)
        out = np.zeros(len(a), np.uint64)
        for i in range(8):
            out = (out << np.uint64(8)) | b[:, i]
        return out
    if a.dtype.kind == "M":
        a = a.astype("datetime64[us]").astype(np.int64)
    if a.dtype.kind == "f":
        b = a.astype(np.float64)
        b = np.where(b == 0, 0.0, b)            # -0.0 groups with +0.0
        b = np.where(np.isnan(b), np.nan, b)    # one NaN payload
        bits = b.view(np.uint64)
        neg = (bits >> np.uint64(63)) == 1
        return np.where(neg, ~bits, bits | _U63)
    if a.dtype.kind == "b":
        return a.astype(np.uint64)
    if a.dtype.kind == "u":
        return a.astype(np.uint64)
    return a.astype(np.int64).view(np.uint64) ^ _U63  # signed bias


def _codepoints(a: np.ndarray, width: Optional[int] = None):
    """[n, width] uint32 codepoint matrix of a string-ish array (None for
    empty input)."""
    if len(a) == 0:
        return None
    u = a.astype("U" if width is None else f"U{width}")
    w = max(u.dtype.itemsize // 4, 1)
    return np.ascontiguousarray(u).view(np.uint32).reshape(len(a), w)


def _mix_u64(h: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer (uint64 wraparound arithmetic)."""
    h = np.asarray(h, np.uint64)
    h = (h ^ (h >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    h = (h ^ (h >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return h ^ (h >> np.uint64(31))


def _row_hash_u64(a: np.ndarray) -> np.ndarray:
    """Full-content hash of one key column: unlike the planning prefix,
    DISTINCT string keys sharing a long prefix hash apart, so hash-mode
    passes fan out even when range-mode prefixes collapse.

    NUL codepoints are SKIPPED, not mixed: the codepoint matrix is padded
    to the array's max string length, so mixing the padding would make the
    same string hash differently on sides with different max lengths
    (equal keys would land in different passes and matches would silently
    drop).  Skipping keys the hash to the non-NUL codepoint sequence only
    — a deterministic function of the string value on every side."""
    a = np.asarray(a)
    if a.dtype.kind in ("U", "S", "O"):
        cp = _codepoints(a)
        if cp is None:
            return np.zeros(0, np.uint64)
        h = np.zeros(len(a), np.uint64)
        for i in range(cp.shape[1]):
            c = cp[:, i].astype(np.uint64)
            h = np.where(c == 0, h, _mix_u64(h ^ c))
        return h
    return _mix_u64(_key_prefix_u64(a))


def _hash_u64_cols(key_cols: Sequence[np.ndarray]) -> np.ndarray:
    """Combined full-content uint64 hash of a key-column tuple — the raw
    value behind hash-mode pass ids, also used by `_RefinablePlan` to
    subdivide passes (h % 2P refines h % P)."""
    h = _row_hash_u64(key_cols[0])
    for col in key_cols[1:]:
        h = _mix_u64(h ^ _row_hash_u64(col))
    return h


def _hash_pass_ids(key_cols: Sequence[np.ndarray], passes: int) -> np.ndarray:
    return (_hash_u64_cols(key_cols) % np.uint64(passes)).astype(np.int64)


_PLAN_SAMPLE = 1 << 20


def _plan_pass_ids(keys_l: Sequence[np.ndarray], keys_r: Sequence[np.ndarray],
                   passes: int, mode: str):
    """-> (pass_id_l, pass_id_r, n_passes, mode_used).

    range: sample-quantile edges over the FIRST key column's prefix, so
    passes inherit the reference's range-partition planning shape
    (arrow_partition_kernels.hpp:394-519 sample+histogram) on the host.
    hash: splitmix over all key columns' full content.  auto: range, then
    hash if the largest planned pass exceeds 3x its fair share OR the
    prefix edges fan out less than the (sampled) distinct keys allow —
    e.g. long-common-prefix strings, where range planning degenerates but
    full-content hashing still splits."""
    if mode not in ("range", "hash", "auto"):
        raise CylonError(Code.Invalid, f"bad chunk mode {mode!r}")
    n_l, n_r = len(keys_l[0]), len(keys_r[0])
    total = n_l + n_r
    passes = max(1, min(passes, max(total, 1)))
    if passes == 1 or total == 0:
        return (np.zeros(n_l, np.int32), np.zeros(n_r, np.int32), 1,
                "range" if mode == "auto" else mode)

    stride_l = max(1, (2 * n_l) // _PLAN_SAMPLE)
    stride_r = max(1, (2 * n_r) // _PLAN_SAMPLE)
    if mode in ("range", "auto"):
        pref_l0 = _key_prefix_u64(keys_l[0])
        pref_r0 = _key_prefix_u64(keys_r[0])
        # per-side strided samples (never a full-input concat: at 1B rows
        # that transient would cost gigabytes of host RAM)
        parts = [a[::st] for a, st in ((pref_l0, stride_l),
                                       (pref_r0, stride_r)) if len(a)]
        s = np.sort(np.concatenate(parts))
        pick = np.linspace(0, len(s) - 1, passes + 1)[1:-1].astype(np.int64)
        edges = np.unique(s[pick])
        edges = edges[edges > s[0]]  # an edge at the min would make an
        n_passes = len(edges) + 1    # unconditionally-empty first pass
        pid_l = np.searchsorted(edges, pref_l0, "right").astype(np.int32)
        pid_r = np.searchsorted(edges, pref_r0, "right").astype(np.int32)
        if mode == "range":
            return pid_l, pid_r, n_passes, "range"
        biggest = max(np.bincount(pid_l, minlength=n_passes).max(initial=0),
                      np.bincount(pid_r, minlength=n_passes).max(initial=0))
        fair = max(n_l, n_r) / n_passes
        # sampled distinct-key estimate bounds what any partitioner can do
        hs = [_hash_pass_ids([c[::st] for c in cols], 1 << 62)
              for cols, st in ((keys_l, stride_l), (keys_r, stride_r))
              if len(cols[0])]
        d_hash = len(np.unique(np.concatenate(hs))) if hs else 1
        if biggest <= 3 * fair + 64 and n_passes >= min(passes, d_hash):
            return pid_l, pid_r, n_passes, "range"
        passes = min(passes, max(d_hash, 1))
        if passes == 1:
            return pid_l, pid_r, n_passes, "range"
    return (_hash_pass_ids(keys_l, passes).astype(np.int32),
            _hash_pass_ids(keys_r, passes).astype(np.int32),
            passes, "hash")


# ---------------------------------------------------------------------------
# key/agg resolution helpers
# ---------------------------------------------------------------------------

def _resolve_keys(names, on, side_on, label):
    keys = side_on if side_on is not None else on
    if keys is None:
        raise CylonError(Code.Invalid, "join requires on= or left_on=/right_on=")
    if isinstance(keys, (str, int)):
        keys = [keys]
    out = []
    for k in keys:
        if isinstance(k, (int, np.integer)):
            if not 0 <= k < len(names):
                raise CylonError(Code.KeyError, f"no {label} column {k}")
            out.append(names[k])
        elif k in names:
            out.append(k)
        else:
            raise CylonError(Code.KeyError, f"no {label} column named {k!r}")
    return out


def _check_key_dtypes(arrs_l, lon, arrs_r, ron):
    from . import dtypes

    for ln, rn in zip(lon, ron):
        a, b = np.asarray(arrs_l[ln]), np.asarray(arrs_r[rn])
        kind = dtypes.join_key_mismatch(
            a.dtype.kind in "USO", b.dtype.kind in "USO",
            a.dtype == b.dtype, len(a) == 0 or len(b) == 0)
        if kind is not None:
            raise CylonError(
                Code.Invalid,
                f"join key type mismatch: {ln}:{a.dtype} vs {rn}:{b.dtype} "
                f"(cast the keys to a common type)")


def _joined_names(names_l, names_r, cfg: JoinConfig) -> List[str]:
    """left names ++ right names, prefixing collisions (reference:
    join_utils.cpp build_final_table naming; mirrors table._join_output_names)."""
    collisions = set(names_l) & set(names_r)
    out_l = [cfg.left_prefix + n if n in collisions else n for n in names_l]
    out_r = [cfg.right_prefix + n if n in collisions else n for n in names_r]
    return out_l + out_r


def _normalize_agg(agg, joined_names) -> List[Tuple[str, AggOp]]:
    """{col: op|[ops]} -> ordered [(joined column name, AggOp)]."""
    out = []
    for ref, ops in agg.items():
        if isinstance(ref, (int, np.integer)):
            ref = joined_names[ref]
        if ref not in joined_names:
            raise CylonError(Code.KeyError, f"no joined column named {ref!r}")
        if isinstance(ops, (str, AggOp)):
            ops = [ops]
        for op in ops:
            out.append((ref, AggOp.of(op)))
    return out


_PARTIAL_FILL = {AggOp.SUM: 0, AggOp.SUMSQ: 0, AggOp.COUNT: 0}


def _partials_for(aggs: List[Tuple[str, AggOp]]) -> List[Tuple[str, AggOp]]:
    """Distinct partial (column, op) pairs needed to reconstruct ``aggs``
    across passes; a COUNT partial is always carried per value column so
    the final combine can mask all-null groups."""
    seen: List[Tuple[str, AggOp]] = []
    for name, op in aggs:
        if op == AggOp.NUNIQUE:
            raise CylonError(
                Code.NotImplemented,
                "NUNIQUE across non-final chunk passes is unsupported: "
                "group by the partitioning key (or use passes=1)")
        for pop in groupby_mod.partial_ops(op):
            if (name, pop) not in seen:
                seen.append((name, pop))
        if (name, AggOp.COUNT) not in seen:
            seen.append((name, AggOp.COUNT))
    return seen


def _numeric_fill(arr: np.ndarray, pop: AggOp, src_dtype) -> np.ndarray:
    """Partial columns come back object-typed when a pass had all-null
    groups; refill with the combine identity so they re-upload numeric."""
    if arr.dtype != object:
        return arr
    mask = np.asarray([v is None for v in arr])
    if pop in (AggOp.MIN, AggOp.MAX):
        if np.issubdtype(src_dtype, np.floating):
            fill = np.inf if pop == AggOp.MIN else -np.inf
        elif np.issubdtype(src_dtype, np.integer):
            info = np.iinfo(src_dtype)
            fill = info.max if pop == AggOp.MIN else info.min
        else:
            raise CylonError(
                Code.NotImplemented,
                f"cross-pass {pop.name} combine over all-null groups of "
                f"dtype {src_dtype} — cast the value column to int/float "
                f"or group by the partitioning key")
        out = np.where(mask, fill, arr).astype(src_dtype)
    else:
        out = np.where(mask, _PARTIAL_FILL.get(pop, 0), arr)
        out = out.astype(np.float64 if pop in (AggOp.SUM, AggOp.SUMSQ)
                         else np.int64)
    return out


#: public name (PR 19): the streaming layer reloads persisted partial-
#: aggregate spills through the same identity-refill as the chunked
#: combine, so a stream state roundtrip and a cross-pass combine can
#: never disagree on what an all-null partial means
numeric_fill = _numeric_fill


# ---------------------------------------------------------------------------
# the chunked engine
# ---------------------------------------------------------------------------

def _passes_final(how: JoinType, mode: str, key_positions, nkeys: int) -> bool:
    """True when per-pass group-bys are final (no cross-pass combine):
    equal group tuples must imply equal pass ids.  ``key_positions`` maps
    key position -> set of copies ('l'/'r') present among group columns."""
    need = range(1) if mode == "range" else range(nkeys)
    for pos in need:
        copies = key_positions.get(pos, set())
        if how == JoinType.INNER:
            ok = bool(copies)          # both copies equal on inner rows
        elif how == JoinType.LEFT:
            ok = "l" in copies         # r-copy is null on unmatched rows
        elif how == JoinType.RIGHT:
            ok = "r" in copies
        else:                          # FULL: either copy may be null
            ok = copies == {"l", "r"}
        if not ok:
            return False
    return True


def _str_width(arr: np.ndarray) -> int:
    enc, _, _ = colmod._encode_strings(np.asarray(arr))
    return max(int(enc.dtype.itemsize), 1)


class _SideBuilder:
    """Builds one side's per-pass device columns with pass-invariant
    shapes (shared capacity, fixed string widths) so every pass hits the
    same compiled program."""

    def __init__(self, names, arrs, pass_ids, cap):
        self.names = names
        self.arrs = arrs
        self.pass_ids = pass_ids
        self.cap = cap
        self.widths = {n: _str_width(a) for n, a in arrs.items()
                       if np.asarray(a).dtype.kind in "USO"}
        # pre-group rows by pass id ONCE (stable order preserves each
        # pass's original row order): chunks become contiguous slices, so
        # total host scan work is O(n) per column instead of the mask
        # path's O(n * passes) — material for 16-pass 1B-row runs on one
        # host core.  Costs one sorted copy per column (the box has the
        # RAM; CYLON_TPU_CHUNK_PRESORT=0 reverts to masking).
        pid = np.asarray(pass_ids)
        self.presort = (config.knob("CYLON_TPU_CHUNK_PRESORT")
                        and int(pid.max(initial=0)) > 0)
        # single-pass plans skip the grouped copy: the identity argsort +
        # full-column gather would duplicate the whole table for nothing
        if self.presort:
            order = np.argsort(pid, kind="stable")
            counts = np.bincount(pid, minlength=int(pid.max(initial=0)) + 1)
            self._offsets = np.concatenate(
                [[0], np.cumsum(counts)]).astype(np.int64)
            self._grouped = {n: np.asarray(a)[order]
                             for n, a in arrs.items()}

    def chunk(self, p: int, only: Optional[Sequence[str]] = None):
        if self.presort:
            if p + 1 < len(self._offsets):
                lo, hi = int(self._offsets[p]), int(self._offsets[p + 1])
            else:
                lo = hi = 0  # pass beyond every planned id: empty chunk
            cols = [colmod.from_numpy(
                self._grouped[n][lo:hi], capacity=self.cap,
                string_width=self.widths.get(n, colmod.DEFAULT_STRING_WIDTH))
                for n in (only if only is not None else self.names)]
            return tuple(cols), jnp.asarray(hi - lo, jnp.int32)
        sel = self.pass_ids == p
        cols, n_sel = [], 0
        for n in (only if only is not None else self.names):
            a = np.asarray(self.arrs[n])[sel]
            n_sel = a.shape[0]
            cols.append(colmod.from_numpy(
                a, capacity=self.cap,
                string_width=self.widths.get(n, colmod.DEFAULT_STRING_WIDTH)))
        return tuple(cols), jnp.asarray(n_sel, jnp.int32)

    def empty_chunk(self, only: Optional[Sequence[str]] = None):
        """Zero-count chunk with the SAME shapes as every real chunk —
        compiles the pass program without re-paying a host compression
        pass over the largest chunk."""
        cols = []
        for n in (only if only is not None else self.names):
            a = np.asarray(self.arrs[n])[:0]
            cols.append(colmod.from_numpy(
                a, capacity=self.cap,
                string_width=self.widths.get(n, colmod.DEFAULT_STRING_WIDTH)))
        return tuple(cols), jnp.asarray(0, jnp.int32)


def _null_mask(a: np.ndarray):
    """Host null mask matching Column.from_numpy's validity inference
    (NaN floats, NaT datetimes, None/NaN objects), or None."""
    if a.dtype.kind == "f":
        return np.isnan(a)
    if a.dtype.kind in "Mm":
        return np.isnat(a)
    if a.dtype.kind == "O":
        try:
            import pandas as pd

            return np.asarray(pd.isna(a), bool)
        except ImportError:
            return np.asarray([x is None for x in a])
    return None


# Optional per-pass progress callback: (passes_done, n_passes,
# out_rows_so_far, run_seconds_so_far).  Set by measurement drivers so a
# deadline mid-sweep still yields an honest partial throughput from the
# COMPLETED passes; None costs nothing.
PASS_PROGRESS_HOOK = None


def _notify_progress(done, n_passes, total, secs) -> None:
    """Invoke PASS_PROGRESS_HOOK non-fatally: a broken progress observer
    must never kill a 64-pass run — it is warned about once and disabled
    for the rest of the process."""
    global PASS_PROGRESS_HOOK
    hook = PASS_PROGRESS_HOOK
    if hook is None:
        return
    try:
        hook(done, n_passes, total, secs)
    except Exception as e:
        import warnings

        PASS_PROGRESS_HOOK = None
        warnings.warn(f"PASS_PROGRESS_HOOK raised {type(e).__name__}: {e}; "
                      f"progress reporting disabled", RuntimeWarning)


def _compose_guards(*guards):
    """One pass-boundary guard from several optional ones (elastic epoch
    checks, serve-layer cancellation/deadline) — None when all are None,
    the single guard unwrapped, else a caller running them in order."""
    gs = [g for g in guards if g is not None]
    if not gs:
        return None
    if len(gs) == 1:
        return gs[0]

    def guard():
        for g in gs:
            g()
    return guard


class _RefinablePlan:
    """Key-domain pass plan that can subdivide its REMAINING parts when a
    pass exceeds device memory.

    Level-``l`` pass ids are ``pid0 + P0 * (q % 2**l)`` over ``P0 * 2**l``
    parts, so part ``p`` at level ``l`` splits into ``{p, p + P0*2**l}``
    at level ``l+1`` — completed parts keep their frames, only unfinished
    key-domain parts re-run at the finer granularity.

    ``q`` (lazy — costs one host hash pass, paid only on the first OOM):
    hash plans use ``q = h // P0`` so the refined id equals ``h % (P0 *
    2**l)``, the splitmix64 partitioner's natural modulus refinement;
    range plans hash the first key column's order-preserving prefix, so
    the refined id stays a function of the FIRST key alone and
    `_passes_final`'s range-mode finality reasoning survives refinement.
    Either way equal keys share ``q`` on both sides, so refined parts
    still partition the key domain and every per-pass result stays exact.
    """

    def __init__(self, pid_l, pid_r, n_passes: int, mode_used: str,
                 keys_l, keys_r):
        self.pid0_l = np.asarray(pid_l)
        self.pid0_r = np.asarray(pid_r)
        self.p0 = int(n_passes)
        self.mode = mode_used
        self._keys_l = keys_l
        self._keys_r = keys_r
        self._q = None
        self._pid_cache = None  # (level, (pid_l, pid_r)) — one level only

    def _q_for(self, keys, pid0) -> np.ndarray:
        if not keys or len(keys[0]) == 0:
            return np.zeros(len(pid0), np.uint64)
        if self.mode == "hash":
            return _hash_u64_cols(keys) // np.uint64(self.p0)
        return _mix_u64(_key_prefix_u64(keys[0]))

    def part_count(self, level: int) -> int:
        return self.p0 << level

    def pids(self, level: int):
        """(pass_id_l, pass_id_r) int arrays at refinement ``level``.
        The last computed level is memoized: during one OOM recovery the
        redistribution checks and the rebuild all ask for the same level,
        and recomputing would materialize fresh full-table arrays at the
        exact moment the host is under memory pressure."""
        if level == 0:
            return self.pid0_l, self.pid0_r
        if self._pid_cache is not None and self._pid_cache[0] == level:
            return self._pid_cache[1]
        if self._q is None:
            self._q = (self._q_for(self._keys_l, self.pid0_l),
                       self._q_for(self._keys_r, self.pid0_r))
        mask = np.uint64((1 << level) - 1)
        ql, qr = self._q
        pid_l = (self.pid0_l.astype(np.int64)
                 + self.p0 * (ql & mask).astype(np.int64))
        pid_r = (self.pid0_r.astype(np.int64)
                 + self.p0 * (qr & mask).astype(np.int64))
        self._pid_cache = (level, (pid_l, pid_r))
        return pid_l, pid_r

    def split(self, parts: List[int], level: int) -> List[int]:
        """Subdivide each of ``parts`` (ids at ``level``) into its two
        children at ``level + 1``, keeping sibling adjacency."""
        c = self.part_count(level)
        return [s for p in parts for s in (p, p + c)]

    def max_part_rows(self, parts: List[int], level: int) -> Tuple[int, int]:
        """(max left rows, max right rows) over ``parts`` at ``level`` —
        the quantities that size a rebuild's chunk capacities."""
        if not parts:
            return 0, 0
        pid_l, pid_r = self.pids(level)
        c = self.part_count(level)
        sel = np.asarray(parts, np.int64)
        c_l = np.bincount(pid_l, minlength=c)[sel]
        c_r = np.bincount(pid_r, minlength=c)[sel]
        return int(c_l.max(initial=0)), int(c_r.max(initial=0))

    def parts_redistributing(self, parts: List[int], level: int):
        """Bool array aligned with ``parts``: True where splitting moves
        that part's rows between its two children on either side.  A
        False part is a key-domain atom (one hot key, or one shared
        8-byte prefix in range mode): its rows all land in one child of
        its old size, so no refinement depth can shrink it."""
        sel = np.asarray(parts, np.int64)
        out = np.zeros(len(sel), bool)
        if not parts:
            return out
        c0 = self.part_count(level)
        c1 = self.part_count(level + 1)
        for pid in self.pids(level + 1):
            if len(pid) == 0:
                continue
            cnt = np.bincount(pid, minlength=c1)
            out |= (cnt[sel] > 0) & (cnt[sel + c0] > 0)
        return out


def _stream_recoverable(make_exec, plan, t0, *, policy=None, stats=None,
                        prefetch=True, progress=True, journal=None,
                        parts=None, pass_guard=None):
    """The resilient streaming loop: checkpointed host frames + adaptive
    pass-splitting + bounded transient retry.

    ``make_exec(parts, level)`` builds one level's execution — builders
    and capacities sized over the REMAINING ``parts`` only, one compiled
    program — returning ``(chunk, prog, fetch)``.  Completed parts' host
    frames are kept across rebuilds, so recovery RESUMES the stream at
    the failed part instead of restarting it.

    With a ``journal`` (`durable.RunJournal`) the checkpoint outlives the
    process: every completed pass's frame spills to disk and is recorded
    in the run manifest, parts the journal already holds are LOADED
    instead of re-executed (``stats["passes_skipped"]``, metric
    ``durable.passes_skipped``) — a fresh process re-invoking the same
    fingerprinted run resumes mid-plan, surviving ``kill -9``.  A fully
    journaled run never even compiles.

    Failure handling, by classified code (`Status.from_exception`):
    - `Code.OutOfMemory` — every remaining part splits in two (``plan``)
      and the level's execution is rebuilt at roughly half the chunk
      capacity; bounded by ``CYLON_TPU_MAX_OOM_SPLITS``, after which a
      `CylonError(Code.OutOfMemory)` is raised.  ``plan=None`` (callers
      whose pass order is not refinable, e.g. the global sort) disables
      splitting and propagates the failure.
    - `Code.ExecutionError` / `Code.Timeout` (transient comm, or a pass
      deadline fired by ``durable.pass_deadline``) — the failing part
      retries in place under ``policy``'s exponential backoff.
    - anything else — propagates unchanged (a TypeError stays a bug).

    Elastic execution (PR 6): ``parts`` restricts the stream to a subset
    of the plan's level-0 part ids (this process's slice of an elastic
    gang; part ids stay GLOBAL so the shared journal is coherent across
    ranks and world sizes).  ``pass_guard`` is called before every pass;
    ANY exception it raises (elastic `EpochChanged`/`CoordinatorLost`,
    the serve layer's cancellation or request-budget Timeout) abandons
    the stream and propagates unchanged — guard raises never enter the
    retry/split/quarantine machinery, whatever their code.

    Poison-pass quarantine (``CYLON_TPU_QUARANTINE_AFTER`` = N > 0): a
    head part failing with the SAME classified code N consecutive times
    is dropped from the stream and reported in ``stats["quarantined"]``
    (and the journal) instead of wedging retries/refinement forever.
    Only recoverable codes qualify — an unknown code stays a bug.

    Returns ``(t_plan, t_run0, frames, total)`` like the old fixed loop.
    """
    policy = policy or resilience.RetryPolicy.from_env()
    stats = stats if stats is not None else {}
    max_splits = resilience.max_oom_splits() if plan is not None else 0
    n_parts0 = plan.part_count(0) if plan is not None else None
    prefetch = prefetch and config.knob("CYLON_TPU_PREFETCH")

    frames: List[Dict[str, np.ndarray]] = []
    total = 0
    if parts is not None and n_parts0 is not None:
        remaining = sorted(int(p) for p in parts if 0 <= int(p) < n_parts0)
    else:
        remaining = list(range(n_parts0)) if n_parts0 is not None else None
    level = 0
    part_retries = 0  # transient retries of the current head part
    atom_watch: set = set()  # child ids of a head atom already split once
    fail_key = None  # (code, level, head part): quarantine failure tracking
    fail_count = 0
    t_plan = None
    t_run0 = time.perf_counter()
    exec_cache: Dict[int, tuple] = {}
    if journal is not None:
        stats.setdefault("passes_skipped", 0)

    def consume_journaled(part: int, hit) -> None:
        """Append a journal-loaded pass frame in place of executing it.
        Serving a part IS completing it, so the head-part retry/failure
        state resets exactly as it would after an executed pass — the
        next part must start with its full budgets."""
        nonlocal total, part_retries, fail_key, fail_count
        frame, n = hit
        frames.append(frame)
        total += int(n)
        part_retries = 0
        fail_key, fail_count = None, 0
        stats["passes_skipped"] += 1
        obs_spans.instant("durable.pass_skipped", part=int(part),
                          level=level, rows=int(n))
        obs_metrics.counter_add("durable.passes_skipped")

    def quarantine_head(st: Status, msg: str) -> bool:
        """Isolate the head part into the run report (poison-pass
        quarantine); False when quarantine is off, nothing remains, or
        the code is not a recoverable kind (a TypeError stays a bug)."""
        nonlocal remaining, part_retries, fail_key, fail_count
        if durable.quarantine_after() <= 0 or not remaining:
            return False
        if not (st.code == Code.OutOfMemory
                or st.code in resilience.RETRYABLE_CODES):
            return False
        part = remaining[0]
        entry = {"part": int(part), "level": level, "code": st.code.name,
                 "failures": fail_count, "msg": msg}
        stats.setdefault("quarantined", []).append(entry)
        if journal is not None:
            journal.record_quarantine(level, part, st.code.name, msg)
        obs_spans.instant("exec.part_quarantined", part=int(part),
                          level=level, code=st.code.name)
        obs_metrics.counter_add("quarantine.parts")
        obs_fleet.flight_record("quarantine", part=int(part), level=level,
                                code=st.code.name, error=msg[:200])
        remaining = remaining[1:]
        part_retries = 0
        fail_key, fail_count = None, 0
        return True

    def fatal(code: Code, msg: str) -> CylonError:
        """A classified FATAL stream failure (OOM past the split budget,
        retries/deadline exhausted): dump the flight recorder before the
        raise so the post-mortem exists even when tracing was never
        armed."""
        obs_fleet.flight_record("pass_fatal", code=code.name, level=level,
                                part=int(remaining[0]) if remaining else None,
                                error=msg[:200])
        return CylonError(code, msg)

    def recover(e: Exception) -> None:
        """Adjust (remaining, level) for a recoverable failure or raise."""
        nonlocal remaining, level, part_retries, fail_key, fail_count
        st = Status.from_exception(e)
        if (journal is not None and remaining
                and (st.code == Code.OutOfMemory
                     or st.code in resilience.RETRYABLE_CODES)
                and journal.completed(level, remaining[0])):
            # the failing part's result is already durably journaled (a
            # deadline overrun classified AFTER its commit): the loop
            # re-enters and serves it from the journal — no retry budget,
            # no backoff, no quarantine, cannot be fatal.  Checked FIRST:
            # a part whose correct frame sits in the journal must never
            # be quarantined out of the output
            obs_spans.instant("exec.pass_served_from_journal",
                              part=int(remaining[0]), level=level,
                              code=st.code.name)
            return
        # the counter is keyed to the PART's identity, not just the code:
        # an OOM split advances the level (the head's first child keeps
        # its id one level up), so productive refinement starts a fresh
        # count instead of accumulating toward quarantine
        key = (st.code, level, remaining[0] if remaining else None)
        if key == fail_key:
            fail_count += 1
        else:
            fail_key, fail_count = key, 1
        # poison-pass quarantine fires EARLY once the head has failed the
        # same way N consecutive times, and LATE at any point a failure
        # would otherwise be fatal (retry/split budgets exhausted, atoms)
        # — so the knob works regardless of how it compares to the retry
        # budget, and a poisoned part never wedges or kills the stream
        qn = durable.quarantine_after()
        if qn > 0 and fail_count >= qn and quarantine_head(st, st.msg):
            return
        if st.code == Code.OutOfMemory and plan is not None:
            if level >= max_splits:
                msg = (f"pass still exceeds device memory after {level} "
                       f"pass-doublings (CYLON_TPU_MAX_OOM_SPLITS="
                       f"{max_splits}): {st.msg}")
                if quarantine_head(st, msg):
                    return
                raise fatal(Code.OutOfMemory, msg) from e
            # progress check: a split that moves no rows rebuilds an
            # identically-sized program that must OOM again — fail fast
            # instead of burning the whole split budget on no-ops
            moved = plan.parts_redistributing(remaining, level)
            if not moved.any():
                atom_l, atom_r = plan.max_part_rows(remaining, level)
                msg = (f"splitting cannot shrink the failing pass: the "
                       f"remaining parts (largest {atom_l}+{atom_r} rows) "
                       f"are key-domain atoms (single hot key or shared "
                       f"range prefix): {st.msg}")
                if quarantine_head(st, msg):
                    return
                raise fatal(Code.OutOfMemory, msg) from e
            # the FAILING head part may be an atom even when later parts
            # split: allow it ONE split (a smaller output capacity from
            # the other parts can heal an output-driven OOM), then stop.
            # The atom is tracked by id lineage — a part's first child
            # keeps its id, the second gets id + part_count — so an empty
            # sibling completing in between cannot hide the repeat OOM.
            if not moved[0]:
                head = remaining[0]
                if head in atom_watch:
                    atom_l, atom_r = plan.max_part_rows(remaining[:1],
                                                        level)
                    msg = (f"splitting cannot shrink the failing pass: "
                           f"its {atom_l}+{atom_r} rows are one "
                           f"key-domain atom (single hot key or shared "
                           f"range prefix): {st.msg}")
                    if quarantine_head(st, msg):
                        return
                    raise fatal(Code.OutOfMemory, msg) from e
                atom_watch.clear()
                atom_watch.update((head, head + plan.part_count(level)))
            else:
                atom_watch.clear()
            remaining = plan.split(remaining, level)
            level += 1
            part_retries = 0
            # levels are never revisited after a split: free the coarser
            # levels' builders (each holds presorted host copies of both
            # tables) instead of accumulating one copy per refinement
            # while recovering from memory pressure
            exec_cache.clear()
            stats["oom_splits"] = stats.get("oom_splits", 0) + 1
            obs_spans.instant("exec.oom_split", level=level,
                              remaining_parts=len(remaining))
            obs_metrics.counter_add("oom.refinements")
            return
        if st.code in resilience.RETRYABLE_CODES:
            if part_retries >= policy.max_retries:
                msg = (f"pass retries exhausted after {part_retries + 1} "
                       f"attempts: {st.msg}")
                if quarantine_head(st, msg):
                    return
                raise fatal(st.code, msg) from e
            d = policy.delay(part_retries)
            part_retries += 1
            stats["retries"] = stats.get("retries", 0) + 1
            obs_spans.instant("exec.pass_retry", attempt=part_retries,
                              code=st.code.name)
            obs_metrics.counter_add("retry.attempts")
            if d > 0:
                policy.sleep(d)
            return
        raise e

    while remaining is None or remaining:
        if journal is not None:
            if remaining is None and "passes" in stats:
                remaining = list(range(stats["passes"]))
            # consume the journaled prefix BEFORE building this level's
            # execution: execution is sequential, so a prior (crashed)
            # process's completions at this level always form a prefix —
            # and a fully journaled run must not compile at all
            while remaining:
                hit = journal.load_pass(level, remaining[0])
                if hit is None:
                    break
                consume_journaled(remaining[0], hit)
                remaining = remaining[1:]
            if not remaining:
                break
        try:
            ex = exec_cache.get(level)
            if ex is None:
                ex = make_exec(remaining, level)
                exec_cache[level] = ex
        except Exception as e:
            recover(e)
            continue
        chunk, prog, fetch = ex
        if remaining is None:  # plan-less callers stream positions 0..n-1
            remaining = list(range(stats["passes"]))
        if t_plan is None:
            t_plan = time.perf_counter() - t0
            t_run0 = time.perf_counter()
        cursor = 0
        cur = fut = nxt = None
        guard_exc = None
        try:
            nxt = chunk(remaining[0]) if prefetch else None
            while cursor < len(remaining):
                if pass_guard is not None:
                    # a guard raise (elastic EpochChanged/CoordinatorLost,
                    # serve cancellation or request-budget Timeout)
                    # ABANDONS the stream unconditionally — it never
                    # enters recover(), so a retryable-coded Timeout from
                    # a request budget cannot burn retries or quarantine
                    # healthy parts, and in-flight work is never retried
                    # into a changed world
                    try:
                        pass_guard()
                    except Exception as ge:
                        guard_exc = ge
                        raise
                part = remaining[cursor]
                if journal is not None:
                    hit = journal.load_pass(level, part)
                    if hit is not None:  # rejected-spill gaps re-ran; the
                        consume_journaled(part, hit)  # rest still skips
                        cursor += 1
                        nxt = None  # prefetched chunk was for this part
                        continue
                deadline = durable.pass_deadline()
                with obs_spans.span("exec.pass", part=part,
                                    level=level) as sp:
                    with deadline:
                        resilience.fault_point("pass_dispatch")
                        cur = nxt if nxt is not None else chunk(part)
                        fut = prog(*cur)               # async dispatch
                        nxt = (chunk(remaining[cursor + 1])
                               if prefetch and cursor + 1 < len(remaining)
                               else None)
                        resilience.fault_point("host_fetch")
                        frame, n = fetch(fut)  # blocks; device errors here
                    if obs_spans.events_enabled():
                        sp.set(rows=int(n), bytes=int(sum(
                            a.nbytes for a in frame.values())))
                        obs_metrics.record_hbm_watermark()
                    elif cursor == 0 and obs_spans.enabled():
                        # the watermark gauge is a metrics-side fact, so
                        # aggregate mode populates it too — but sampling
                        # scans every live jax array in the process, so
                        # the always-on default pays it once per level,
                        # not once per pass
                        obs_metrics.record_hbm_watermark()
                committed = False
                if journal is not None:
                    # spill + manifest-commit BEFORE the frame counts as
                    # done: a crash inside the journal write re-runs the
                    # pass on resume (at-least-once, never lost)
                    committed = journal.record_pass(level, part, frame,
                                                    int(n))
                if committed:
                    # a deadline overrun classifies AFTER the late frame
                    # is journaled: the Timeout retry serves the result
                    # from the journal instead of re-executing an
                    # identically-slow pass forever
                    deadline.raise_if_fired()
                else:
                    # no journal to serve a retry from: discarding the
                    # late-but-correct frame would condemn every
                    # consistently-slow pass to retry-until-fatal, so
                    # keep it and record the overrun
                    deadline.accept_late()
                total += n
                frames.append(frame)
                cursor += 1
                part_retries = 0
                fail_key, fail_count = None, 0
                stats["parts_run"] = stats.get("parts_run", 0) + 1
                obs_metrics.counter_add("exec.parts_run")
                cur = fut = None
                if progress:
                    _notify_progress(
                        len(frames), len(frames) + len(remaining) - cursor,
                        total, time.perf_counter() - t_run0)
            remaining = []
        except Exception as e:
            # drop the failed pass's device buffers BEFORE re-planning:
            # this frame stays alive through recover()/make_exec(), and a
            # rebuild warmed while the dead full-size buffers are still
            # resident would re-OOM and burn a split for nothing.  The
            # level's program/builder locals go too — their closures hold
            # full presorted host copies of both sides, and keeping them
            # referenced across make_exec would double host memory at the
            # exact moment we're recovering from pressure
            cur = fut = nxt = None
            chunk = prog = fetch = ex = None
            remaining = remaining[cursor:]  # completed frames are kept
            if guard_exc is e:
                raise
            recover(e)
    if t_plan is None:
        t_plan = time.perf_counter() - t0
    return t_plan, t_run0, frames, total


def _run_passes(prog, empty_chunk, chunk, n_passes, fetch, t0, *,
                policy=None, stats=None, journal=None, pass_guard=None):
    """Streaming loop over positional passes 0..n-1 with transient-retry
    resilience (no OOM splitting: callers on this entry — the global sort
    — emit passes in an order a hash subdivision would scramble).
    Compiles on a zero-count chunk (same shapes, no duplicate host pass
    over the largest chunk), then double-buffers — pass p dispatches
    async while pass p+1's host compression + upload overlap it
    (CYLON_TPU_PREFETCH=0 reverts to strictly serial)."""
    stats = stats if stats is not None else {}
    stats["passes"] = n_passes

    def make_exec(_parts, _level):
        warm = empty_chunk()
        jax.block_until_ready(prog(*warm))
        del warm
        return chunk, prog, fetch

    return _stream_recoverable(make_exec, None, t0, policy=policy,
                               stats=stats, journal=journal,
                               pass_guard=pass_guard)


def _concat_host(frames: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    if not frames:
        return {}
    out = {}
    for name in frames[0]:
        parts = [f[name] for f in frames]
        if any(p.dtype == object for p in parts):
            parts = [p.astype(object) for p in parts]
        out[name] = np.concatenate(parts)
    return out


def chunked_join(left, right, *, on=None, left_on=None, right_on=None,
                 how: str = "inner", passes: int = 4, algo: str = "sort",
                 mode: str = "auto", ctx=None, prefetch: bool = True,
                 left_prefix: str = "l_", right_prefix: str = "r_",
                 elastic=None, pass_guard=None):
    """Out-of-core join over host frames (pandas/dict/Table): the key
    domain is split into ``passes`` parts, each part joined on device by
    one shared compiled program, outputs concatenated on the host.  All
    four join types are exact because parts partition BOTH sides by key.

    ``pass_guard`` (serving layer): called before every pass; raising a
    non-retryable `CylonError` there (Cancelled, Timeout past a request
    budget) stops the stream at the next pass boundary — the in-flight
    pass finishes (and journals) first, so cancellation never loses
    completed work.

    Returns (dict of host columns keyed by joined names, stats)."""
    return _chunked_engine(left, right, on=on, left_on=left_on,
                           right_on=right_on, how=how, group_by=None,
                           agg=None, passes=passes, algo=algo, ddof=0,
                           mode=mode, ctx=ctx, prefetch=prefetch,
                           left_prefix=left_prefix,
                           right_prefix=right_prefix, elastic=elastic,
                           pass_guard=pass_guard)


def chunked_join_groupby_tables(left, right, *, on=None, left_on=None,
                                right_on=None, how: str = "inner",
                                group_by, agg: Dict, passes: int = 4,
                                algo: str = "sort", ddof: int = 0,
                                mode: str = "auto", ctx=None,
                                prefetch: bool = True, elastic=None,
                                pass_guard=None):
    """Out-of-core join + group-by over host frames.  ``group_by`` and
    ``agg`` use POST-JOIN column names (collisions prefixed l_/r_, as
    Table.join names them).  When the group keys pin down the
    partitioning key the per-pass group-bys are final; otherwise each
    pass emits partial aggregation states and one small device group-by
    combines them (the cross-pass analog of the distributed two-phase
    group-by, reference groupby/groupby.cpp:23-73).

    Returns (dict of host columns, stats)."""
    if agg is None or group_by is None:
        raise CylonError(Code.Invalid, "group_by and agg are required")
    return _chunked_engine(left, right, on=on, left_on=left_on,
                           right_on=right_on, how=how, group_by=group_by,
                           agg=agg, passes=passes, algo=algo, ddof=ddof,
                           mode=mode, ctx=ctx, prefetch=prefetch,
                           elastic=elastic, pass_guard=pass_guard)


def _chunked_engine(left, right, *, on, left_on, right_on, how, group_by,
                    agg, passes, algo, ddof, mode, ctx, prefetch,
                    left_prefix: str = "l_", right_prefix: str = "r_",
                    elastic=None, pass_guard=None):
    t_plan0 = time.perf_counter()
    names_l, arrs_l = _as_host_frame(left)
    names_r, arrs_r = _as_host_frame(right)
    lon = _resolve_keys(names_l, on, left_on, "left")
    ron = _resolve_keys(names_r, on, right_on, "right")
    if len(lon) != len(ron):
        raise CylonError(Code.Invalid, "left_on/right_on length mismatch")
    _check_key_dtypes(arrs_l, lon, arrs_r, ron)
    cfg = JoinConfig.of(how, algo, tuple(lon), tuple(ron),
                        left_prefix, right_prefix)
    jt = cfg.join_type
    joined = _joined_names(names_l, names_r, cfg)
    lidx = tuple(names_l.index(n) for n in lon)
    ridx = tuple(names_r.index(n) for n in ron)

    # -- plan passes over the key domain --------------------------------
    keys_l_arr = [np.asarray(arrs_l[n]) for n in lon]
    keys_r_arr = [np.asarray(arrs_r[n]) for n in ron]
    pid_l, pid_r, n_passes, mode_used = _plan_pass_ids(
        keys_l_arr, keys_r_arr, passes, mode)
    counts_l = np.bincount(pid_l, minlength=n_passes)
    counts_r = np.bincount(pid_r, minlength=n_passes)
    cap_l = pow2ceil(int(max(8, counts_l.max(initial=0))))
    cap_r = pow2ceil(int(max(8, counts_r.max(initial=0))))

    # -- group/agg resolution -------------------------------------------
    gb_names, aggs_req, final_per_pass, fuse_pipeline = None, None, True, False
    if group_by is not None:
        if isinstance(group_by, (str, int, np.integer)):
            group_by = [group_by]
        gb_names = []
        for g in group_by:
            if isinstance(g, (int, np.integer)):
                g = joined[g]
            if g not in joined:
                raise CylonError(Code.KeyError,
                                 f"no joined column named {g!r}")
            gb_names.append(g)
        aggs_req = _normalize_agg(agg, joined)
        # which join-key positions do the group columns pin down?
        key_positions: Dict[int, set] = {}
        n_l = len(names_l)
        for g in gb_names:
            gi = joined.index(g)
            if gi < n_l and gi in lidx:
                key_positions.setdefault(lidx.index(gi), set()).add("l")
            elif gi >= n_l and (gi - n_l) in ridx:
                key_positions.setdefault(ridx.index(gi - n_l), set()).add("r")
        final_per_pass = _passes_final(jt, mode_used, key_positions, len(lon))
        # key-grouped fusion: INNER join output is already adjacent on the
        # full key tuple, so group keys forming a PREFIX of the key tuple
        # need no second sort (pipeline group-by instead of hash group-by)
        every_gb_is_key = all(
            (joined.index(g) < n_l and joined.index(g) in lidx)
            or (joined.index(g) >= n_l and (joined.index(g) - n_l) in ridx)
            for g in gb_names)
        positions = sorted(key_positions)
        fuse_pipeline = (jt == JoinType.INNER and final_per_pass
                         and every_gb_is_key and len(positions) >= 1
                         and positions == list(range(len(positions))))

    world = 1 if ctx is None else ctx.GetWorldSize()
    if world > 1:
        if elastic is not None:
            raise CylonError(
                Code.Invalid,
                "elastic execution drives one local mesh per process "
                "(gang re-init on membership change); pass ctx=None — a "
                "live multi-device mesh cannot be reshaped under a run")
        return _chunked_distributed(
            arrs_l, names_l, arrs_r, names_r, lon, ron, cfg, joined,
            pid_l, pid_r, n_passes, counts_l, counts_r, gb_names, aggs_req,
            final_per_pass, agg, ddof, ctx, mode_used, t_plan0,
            pass_guard=pass_guard)

    # -- the one compiled per-pass program (per refinement level) --------
    nk = len(lon)
    kidx = tuple(range(nk))
    if gb_names is not None:
        gidx = tuple(joined.index(g) for g in gb_names)
        if final_per_pass:
            aggs_dev = tuple((joined.index(n), op) for n, op in aggs_req)
            out_names = list(gb_names) + [f"{op.name.lower()}_{n}"
                                          for n, op in aggs_req]
        else:
            partials = _partials_for(aggs_req)
            aggs_dev = tuple((joined.index(n), pop) for n, pop in partials)
            out_names = list(gb_names) + [f"{pop.name.lower()}_{n}"
                                          for n, pop in partials]

    def make_prog(out_cap: int):
        if gb_names is None:
            @jax.jit
            def prog(cl, cnt_l, cr, cnt_r):
                jcols, jm = join_mod.join_gather(cl, cnt_l, cr, cnt_r,
                                                 lidx, ridx, jt, out_cap,
                                                 algo)
                return jcols, jm

            def fetch(out):
                jcols, jm = out
                n = int(jm)
                return {name: colmod.to_numpy(c, n)
                        for name, c in zip(joined, jcols)}, n
        elif fuse_pipeline and final_per_pass:
            @jax.jit
            def prog(cl, cnt_l, cr, cnt_r):
                jcols, jm = join_mod.join_gather(
                    cl, cnt_l, cr, cnt_r, lidx, ridx, jt, out_cap, algo,
                    key_grouped=True)
                return groupby_mod.pipeline_groupby(jcols, jm, gidx,
                                                    aggs_dev, ddof)
        else:
            @jax.jit
            def prog(cl, cnt_l, cr, cnt_r):
                jcols, jm = join_mod.join_gather(
                    cl, cnt_l, cr, cnt_r, lidx, ridx, jt, out_cap, algo)
                return groupby_mod.hash_groupby(jcols, jm, gidx,
                                                aggs_dev, ddof)

        if gb_names is not None:
            def fetch(out):
                gcols, g = out
                n = int(g)
                return {name: colmod.to_numpy(c, n)
                        for name, c in zip(out_names, gcols)}, n
        return prog, fetch

    # -- resilient streaming: build one level's execution over the
    #    REMAINING parts only (capacities shrink as passes split), keep
    #    completed host frames, resume on recoverable failures ----------
    plan = _RefinablePlan(pid_l, pid_r, n_passes, mode_used,
                          keys_l_arr, keys_r_arr)
    policy = ctx.retry_policy() if ctx is not None \
        else resilience.RetryPolicy.from_env()
    stats = {"passes": n_passes, "mode": mode_used,
             "chunk_cap": max(cap_l, cap_r), "cap_l": cap_l, "cap_r": cap_r,
             "world": 1}
    journal = None
    if durable.enabled():
        # run identity: op shape x realized plan x sampled input content
        # x result-affecting knob config — a resumed process recomputes
        # the identical fingerprint and reopens the same journal
        op = "join" if gb_names is None else "join_groupby"
        fp = durable.run_fingerprint(
            op,
            (tuple(lon), tuple(ron), int(jt), int(cfg.algorithm),
             cfg.left_prefix, cfg.right_prefix,
             tuple(gb_names) if gb_names is not None else None,
             tuple((n, int(o)) for n, o in aggs_req)
             if aggs_req is not None else None,
             int(ddof), int(n_passes), mode_used, 1),
            ((names_l, arrs_l), (names_r, arrs_r)))
        # the fingerprint is world-INDEPENDENT by design: an elastic gang
        # at any membership (and a single-process re-invocation) shares
        # one journal; the slice's world/epoch ride the manifest as
        # per-pass provenance only
        journal = durable.open_run(
            fp, op,
            world=None if elastic is None else elastic.world,
            epoch=None if elastic is None else elastic.epoch)

    def make_exec(parts, level):
        pid_l_lvl, pid_r_lvl = plan.pids(level)
        max_l, max_r = plan.max_part_rows(parts, level)
        cap_l_lvl = pow2ceil(max(8, max_l))
        cap_r_lvl = pow2ceil(max(8, max_r))
        build_l = _SideBuilder(names_l, arrs_l, pid_l_lvl, cap_l_lvl)
        build_r = _SideBuilder(names_r, arrs_r, pid_r_lvl, cap_r_lvl)
        # exact output sizing over key columns only (the reference's
        # two-pass builder Reserve, join_utils.cpp), remaining parts only
        m_max = 0
        for p in parts:
            kc_l, cnt_l = build_l.chunk(p, only=lon)
            kc_r, cnt_r = build_r.chunk(p, only=ron)
            m = int(join_mod.join_row_count(kc_l, cnt_l, kc_r, cnt_r,
                                            kidx, kidx, jt, algo))
            m_max = max(m_max, m)
            del kc_l, kc_r
        out_cap = pow2ceil(max(8, m_max))
        stats.update(chunk_cap=max(cap_l_lvl, cap_r_lvl), cap_l=cap_l_lvl,
                     cap_r=cap_r_lvl, out_cap=out_cap)
        prog, fetch = make_prog(out_cap)

        def chunk(p):
            return build_l.chunk(p) + build_r.chunk(p)

        # compile + warm on the first remaining pass so run_seconds is
        # steady-state
        args0 = chunk(parts[0])
        jax.block_until_ready(prog(*args0))
        del args0
        return chunk, prog, fetch

    t_plan, t_run0, frames, total = _stream_recoverable(
        make_exec, plan, t_plan0, policy=policy, stats=stats,
        prefetch=prefetch, journal=journal,
        parts=None if elastic is None else elastic.parts,
        pass_guard=_compose_guards(
            None if elastic is None else elastic.guard, pass_guard))
    if journal is not None and not stats.get("quarantined"):
        # every pass the plan needed is journaled: the run is a complete
        # result-cache entry, and the cap GC may now reclaim older runs
        journal.record_done(len(frames), total)
        durable.gc_journal()
    result = _concat_host(frames)
    if gb_names is not None and not final_per_pass:
        result, total = _combine_partials(result, gb_names, aggs_req,
                                          arrs_l, arrs_r, names_l, names_r,
                                          joined, ddof, ctx)
    t_run = time.perf_counter() - t_run0
    stats["groups" if gb_names is not None else "rows"] = total
    stats["plan_seconds"] = t_plan
    stats["run_seconds"] = t_run
    # cold-run honesty (round-3 advice): the exact-sizing pass inside
    # plan_seconds re-reads the whole input, so a throughput from
    # run_seconds alone understates one-shot cost by ~one data pass
    stats["total_seconds"] = t_plan + t_run
    return result, stats


# ---------------------------------------------------------------------------
# cross-pass partial combine
# ---------------------------------------------------------------------------

def _combine_partials(partial_result, gb_names, aggs_req, arrs_l, arrs_r,
                      names_l, names_r, joined, ddof, ctx):
    """One small device group-by over the concatenated per-pass partial
    states, then host arithmetic derives the requested aggregates
    (MEAN/VAR/STDDEV from SUM/COUNT/SUMSQ — reference KernelTraits
    decomposition, compute/aggregate_kernels.hpp:38-200)."""
    from .context import default_context
    from .table import Table

    def src_dtype(joined_name):
        i = joined.index(joined_name)
        if i < len(names_l):
            return np.asarray(arrs_l[names_l[i]]).dtype
        return np.asarray(arrs_r[names_r[i - len(names_l)]]).dtype

    partials = _partials_for(aggs_req)
    filled = dict(partial_result)
    for name, pop in partials:
        col = f"{pop.name.lower()}_{name}"
        filled[col] = _numeric_fill(np.asarray(filled[col]), pop,
                                    src_dtype(name))
    t = Table.from_numpy(list(filled), list(filled.values()),
                         ctx=ctx or default_context())
    combine_agg = {f"{pop.name.lower()}_{name}":
                   [groupby_mod.combine_op(pop)] for name, pop in partials}
    out = t.groupby(gb_names, combine_agg).to_numpy()

    def comb(name, pop):
        c = groupby_mod.combine_op(pop)
        return np.asarray(
            out[f"{c.name.lower()}_{pop.name.lower()}_{name}"])

    result = {g: out[g] for g in gb_names}
    for name, op in aggs_req:
        n = comb(name, AggOp.COUNT).astype(np.float64)
        label = f"{op.name.lower()}_{name}"
        if op == AggOp.COUNT:
            result[label] = n.astype(np.int64)
            continue
        empty = n == 0
        with np.errstate(invalid="ignore", divide="ignore"):
            if op == AggOp.SUM:
                v = comb(name, AggOp.SUM)
                if np.issubdtype(src_dtype(name), np.integer):
                    v = np.where(empty, 0, v).astype(np.int64)
            elif op in (AggOp.MIN, AggOp.MAX):
                v = comb(name, op)
            elif op == AggOp.MEAN:
                v = comb(name, AggOp.SUM) / np.maximum(n, 1)
            elif op in (AggOp.VAR, AggOp.STDDEV):
                s, s2 = comb(name, AggOp.SUM), comb(name, AggOp.SUMSQ)
                nn = np.maximum(n, 1)
                v = np.maximum((s2 - s * s / nn) / np.maximum(nn - ddof, 1), 0)
                if op == AggOp.STDDEV:
                    v = np.sqrt(v)
                empty = empty | (n - ddof <= 0)
            else:
                raise CylonError(Code.NotImplemented, f"combine {op.name}")
        if empty.any():
            v = v.astype(object)
            v[empty] = None
        result[label] = v
    return result, len(next(iter(out.values())) if out else [])


# ---------------------------------------------------------------------------
# distributed per-pass execution (each pass sharded over the mesh)
# ---------------------------------------------------------------------------

def _chunked_distributed(arrs_l, names_l, arrs_r, names_r, lon, ron, cfg,
                         joined, pid_l, pid_r, n_passes, counts_l, counts_r,
                         gb_names, aggs_req, final_per_pass, agg, ddof, ctx,
                         mode_used, t_plan0, pass_guard=None):
    """Every key-domain pass sharded over ``ctx``'s mesh via the public
    distributed operators — total capacity is passes x mesh-HBM (the
    composition of the reference's rank scaling, docs/docs/arch.md:146-162,
    with range streaming)."""
    from .table import Table

    world = ctx.GetWorldSize()
    shard_cap = pow2ceil(int(max(
        8, -(-int(counts_l.max(initial=0)) // world),
        -(-int(counts_r.max(initial=0)) // world))))
    cap = shard_cap * world
    how = {JoinType.INNER: "inner", JoinType.LEFT: "left",
           JoinType.RIGHT: "right", JoinType.FULL_OUTER: "outer"}[cfg.join_type]

    if gb_names is not None:
        if final_per_pass:
            pass_agg = {}
            for name, op in aggs_req:
                pass_agg.setdefault(name, []).append(op)
        else:
            pass_agg = {}
            for name, pop in _partials_for(aggs_req):
                pass_agg.setdefault(name, []).append(pop)

    t_plan = time.perf_counter() - t_plan0
    t_run0 = time.perf_counter()
    frames = []
    total = 0
    # each pass is a fresh collective program over the mesh; retrying it
    # is only mesh-safe single-process (see collective_retry_policy)
    policy = ctx.collective_retry_policy()
    retries = 0

    def run_pass(p: int):
        resilience.fault_point("pass_dispatch")
        sel_l = pid_l == p
        sel_r = pid_r == p
        lt = Table.from_numpy(names_l, [np.asarray(arrs_l[n])[sel_l]
                                        for n in names_l], ctx=ctx,
                              capacity=cap)
        rt = Table.from_numpy(names_r, [np.asarray(arrs_r[n])[sel_r]
                                        for n in names_r], ctx=ctx,
                              capacity=cap)
        j = lt.distributed_join(rt, left_on=lon, right_on=ron, how=how,
                                algorithm=cfg.algorithm)
        if gb_names is None:
            return j.to_numpy(), j.row_count
        g = j.groupby(gb_names, pass_agg, ddof=ddof)
        return g.to_numpy(), g.row_count

    for p in range(n_passes):
        if pass_guard is not None:
            # serve-layer cancellation/deadline: stop at the next pass
            # boundary — completed frames were already fetched, nothing
            # in-flight is abandoned mid-collective
            pass_guard()
        # transient (comm/deadline) failures retry the PASS, not the whole
        # stream: completed frames are the checkpoint
        (frame, n), attempts = resilience.retry_call(
            lambda p=p: run_pass(p), policy=policy,
            site=f"distributed pass {p}/{n_passes}")
        retries += attempts - 1
        frames.append(frame)
        total += n
        _notify_progress(p + 1, n_passes, total,
                         time.perf_counter() - t_run0)
    result = _concat_host(frames)
    if gb_names is not None and not final_per_pass:
        result, total = _combine_partials(result, gb_names, aggs_req,
                                          arrs_l, arrs_r, names_l, names_r,
                                          joined, ddof, ctx)
    t_run = time.perf_counter() - t_run0
    from .parallel import plane as plane_mod

    # every mesh pass shuffles through parallel.ops; record which exchange
    # realization (packed plane vs per-buffer) the artifact was measured
    # under — the battery's A/B arms depend on this being in the ledger
    stats = {"passes": n_passes, "mode": mode_used, "world": world,
             "shard_cap": shard_cap, "retries": retries,
             "shuffle_pack": plane_mod.pack_enabled(),
             "groups" if gb_names is not None else "rows": total,
             "plan_seconds": t_plan, "run_seconds": t_run,
             "total_seconds": t_plan + t_run}
    return result, stats


# ---------------------------------------------------------------------------
# standalone out-of-core operators (no join): group-by and sort
# ---------------------------------------------------------------------------

def chunked_groupby(data, by, agg: Dict, *, passes: int = 4, ddof: int = 0,
                    mode: str = "auto", ctx=None, elastic=None,
                    pass_guard=None):
    """Out-of-core group-by over one host frame: the key domain is
    partitioned on the GROUP columns themselves, so every pass's
    group-by is final (a group never spans passes) and the results just
    concatenate — the single-frame analog of the distributed two-phase
    group-by's shuffle-on-keys (reference groupby/groupby.cpp:23-73).

    Returns (dict of host columns, stats)."""
    t0 = time.perf_counter()
    names, arrs = _as_host_frame(data)
    by_names = _resolve_keys(names, by, None, "group")
    aggs_req = _normalize_agg(agg, names)
    key_arrs = [np.asarray(arrs[n]) for n in by_names]
    empty = [np.zeros(0, a.dtype) for a in key_arrs]
    pid, _, n_passes, mode_used = _plan_pass_ids(key_arrs, empty, passes, mode)
    counts = np.bincount(pid, minlength=n_passes)
    cap = pow2ceil(int(max(8, counts.max(initial=0))))
    by_idx = tuple(names.index(n) for n in by_names)
    aggs_dev = tuple((names.index(n), op) for n, op in aggs_req)
    out_names = list(by_names) + [f"{op.name.lower()}_{n}"
                                  for n, op in aggs_req]

    world = 1 if ctx is None else ctx.GetWorldSize()
    if world > 1 and elastic is not None:
        raise CylonError(Code.Invalid,
                         "elastic execution drives one local mesh per "
                         "process; pass ctx=None")
    frames: List[Dict[str, np.ndarray]] = []
    total = 0
    if world > 1:
        from .table import Table

        shard_cap = pow2ceil(int(max(8, -(-int(counts.max(initial=0))
                                         // world))))
        pass_agg: Dict[str, list] = {}
        for n, op in aggs_req:
            pass_agg.setdefault(n, []).append(op)
        t_plan = time.perf_counter() - t0
        t_run0 = time.perf_counter()
        for p in range(n_passes):
            if pass_guard is not None:
                pass_guard()
            sel = pid == p
            t = Table.from_numpy(names, [np.asarray(arrs[n])[sel]
                                         for n in names], ctx=ctx,
                                 capacity=shard_cap * world)
            g = t.groupby(by_names, pass_agg, ddof=ddof)
            frames.append(g.to_numpy())
            total += g.row_count
    else:
        def fetch(out):
            gcols, g = out
            n = int(g)
            return {name: colmod.to_numpy(c, n)
                    for name, c in zip(out_names, gcols)}, n

        # the partition keys ARE the group keys, so hash-refining a part
        # never splits a group across passes: full OOM recovery applies
        plan = _RefinablePlan(pid, np.zeros(0, np.int32), n_passes,
                              mode_used, key_arrs, [])
        extra: Dict = {}
        journal = None
        if durable.enabled():
            fp = durable.run_fingerprint(
                "groupby",
                (tuple(by_names),
                 tuple((n, int(o)) for n, o in aggs_req),
                 int(ddof), int(n_passes), mode_used, 1),
                ((names, arrs),))
            journal = durable.open_run(
                fp, "groupby",
                world=None if elastic is None else elastic.world,
                epoch=None if elastic is None else elastic.epoch)

        def make_exec(parts, level):
            pid_lvl, _ = plan.pids(level)
            max_rows, _ = plan.max_part_rows(parts, level)
            cap_lvl = pow2ceil(max(8, max_rows))
            build = _SideBuilder(names, arrs, pid_lvl, cap_lvl)

            @jax.jit
            def prog(cols, cnt):
                return groupby_mod.hash_groupby(cols, cnt, by_idx, aggs_dev,
                                                ddof)

            warm = build.empty_chunk()
            jax.block_until_ready(prog(*warm))
            del warm
            return build.chunk, prog, fetch

        t_plan, t_run0, frames, total = _stream_recoverable(
            make_exec, plan, t0, stats=extra, journal=journal,
            parts=None if elastic is None else elastic.parts,
            pass_guard=_compose_guards(
                None if elastic is None else elastic.guard, pass_guard))
        if journal is not None and not extra.get("quarantined"):
            journal.record_done(len(frames), total)
            durable.gc_journal()
    result = _concat_host(frames)
    t_run = time.perf_counter() - t_run0
    stats = {"passes": n_passes, "mode": mode_used, "world": world,
             "groups": total, "plan_seconds": t_plan,
             "run_seconds": t_run, "total_seconds": t_plan + t_run}
    if world == 1:
        stats.update(extra)
    return result, stats


def chunked_repartition(data, keys, world: int, *, passes: int = 4,
                        out_dir: "str | None" = None, ctx=None):
    """Out-of-core hash repartition of one host frame into ``world`` hash
    shards, streamed through the device in ``passes`` passes — BASELINE
    config 3 ("1B-row hash shuffle / repartition") at beyond-HBM scale on
    one chip.  Each pass rides the SAME kernels as the distributed
    shuffle's local half (reference partition.cpp:24-87 + Split,
    arrow_kernels.hpp:60-96): Pallas murmur3 targets + the stable
    per-target split — so concatenating a target's per-pass slices yields
    exactly the shard the mesh shuffle would deliver to that rank (the
    device hasher is bit-identical to the native host hasher).

    Passes stripe the input by contiguous row blocks (target assignment
    is per-row, so any disjoint pass split is valid — striping keeps the
    host side at slice cost, no selection pass).

    With ``out_dir``, each (target, pass) slice lands in
    ``{out_dir}/shard_{t}/part_{p:04d}.parquet`` and only counts are kept
    in memory; otherwise per-target host columns are returned.

    With a distributed ``ctx`` each pass instead runs the REAL mesh
    shuffle; ``world`` must equal the context's world size (the mesh
    defines the shard count).  On a true multi-HOST mesh the return mode
    covers only this process's shards — use ``out_dir`` (each process
    writes its own shard files, gather-free) for the global result.

    Returns (list of ``world`` per-target host-column dicts | None when
    ``out_dir`` is given, stats)."""
    t0 = time.perf_counter()
    names, arrs = _as_host_frame(data)
    key_names = _resolve_keys(names, keys, None, "partition")
    key_idx = tuple(names.index(n) for n in key_names)
    if world < 1:
        raise CylonError(Code.Invalid, f"world must be >= 1, got {world}")
    n_rows = int(np.asarray(arrs[names[0]]).shape[0]) if names else 0
    n_passes = max(1, min(passes, max(1, n_rows)))
    block = -(-n_rows // n_passes)
    cap = pow2ceil(max(8, block))

    wctx = 1 if ctx is None else ctx.GetWorldSize()
    if out_dir is not None:
        os.makedirs(out_dir, exist_ok=True)
        # a reused out_dir must not mix this run's parts with a prior
        # run's (e.g. an earlier run with more passes): clear OUR layout
        # only — part files under shard_* dirs — never foreign files
        import glob as _glob

        for stale in _glob.glob(os.path.join(out_dir, "shard_*",
                                             "part_*.parquet")):
            os.remove(stale)

    widths = {n: _str_width(a) for n, a in arrs.items()
              if np.asarray(a).dtype.kind in "USO"}

    def slice_chunk(p: int):
        lo, hi = p * block, min((p + 1) * block, n_rows)
        cols = tuple(colmod.from_numpy(
            np.asarray(arrs[n])[lo:hi], capacity=cap,
            string_width=widths.get(n, colmod.DEFAULT_STRING_WIDTH))
            for n in names)
        return cols, jnp.asarray(hi - lo, jnp.int32)

    def empty_chunk():
        cols = tuple(colmod.from_numpy(
            np.asarray(arrs[n])[:0], capacity=cap,
            string_width=widths.get(n, colmod.DEFAULT_STRING_WIDTH))
            for n in names)
        return cols, jnp.asarray(0, jnp.int32)

    acc: "List[List[Dict[str, np.ndarray]]]" = [[] for _ in range(world)]
    per_target = np.zeros(world, np.int64)

    if wctx > 1:
        from .table import Table

        if world != wctx:
            raise CylonError(Code.Invalid,
                             f"world {world} != distributed context world "
                             f"{wctx}: with ctx the mesh defines the shard "
                             f"count")
        if out_dir is not None:
            for t in range(world):
                os.makedirs(os.path.join(out_dir, f"shard_{t}"),
                            exist_ok=True)
        t_plan = time.perf_counter() - t0
        t_run0 = time.perf_counter()
        total = 0
        for p in range(n_passes):
            lo, hi = p * block, min((p + 1) * block, n_rows)
            t = Table.from_numpy(names, [np.asarray(arrs[n])[lo:hi]
                                         for n in names], ctx=ctx,
                                 capacity=cap)
            s = t.shuffle(key_names)
            total += s.row_count
            if out_dir is not None:
                # same shard_{t}/part_{p}.parquet layout as single-chip
                s.to_parquet(os.path.join(out_dir, "shard_{shard}",
                                          f"part_{p:04d}.parquet"),
                             per_shard=True)
                from .table import _host_row_counts

                per_target[:] += np.asarray(_host_row_counts(s),
                                            np.int64)[:world]
            else:
                for sid, scols, cnt in s._addressable_host_shards():
                    frame = {name: colmod.to_numpy(c, cnt)
                             for name, c in zip(names, scols)}
                    per_target[sid] += cnt
                    acc[sid].append(frame)
        result = (None if out_dir is not None
                  else [_concat_host(fs) for fs in acc])
        t_run = time.perf_counter() - t_run0
        from .parallel import plane as plane_mod

        stats = {"passes": n_passes, "world": wctx, "rows": total,
                 "per_target": per_target.tolist(),
                 "shuffle_pack": plane_mod.pack_enabled(),
                 "plan_seconds": t_plan, "run_seconds": t_run,
                 "total_seconds": t_plan + t_run}
        return result, stats

    from .parallel import partition as partition_mod
    from .parallel import shuffle as shuffle_mod

    @jax.jit
    def prog(cols, cnt):
        t = partition_mod.hash_targets(cols, cnt, key_idx, world)
        perm_t, = shuffle_mod._perm_by_target(t, world)
        counts = shuffle_mod.target_counts(t, world)
        grouped = tuple(c.take(perm_t) for c in cols)
        return grouped, counts

    def fetch_and_store(out, p: int) -> int:
        grouped, counts = out
        cnts = np.asarray(jax.device_get(counts))
        n = int(cnts.sum())
        frame = {name: colmod.to_numpy(c, n)
                 for name, c in zip(names, grouped)}
        offs = np.concatenate([[0], np.cumsum(cnts)]).astype(np.int64)
        for t in range(world):
            sl = {name: a[offs[t]:offs[t + 1]] for name, a in frame.items()}
            per_target[t] += offs[t + 1] - offs[t]
            if out_dir is not None:
                import pandas as pd

                d = os.path.join(out_dir, f"shard_{t}")
                os.makedirs(d, exist_ok=True)
                pd.DataFrame(sl).to_parquet(
                    os.path.join(d, f"part_{p:04d}.parquet"))
            else:
                acc[t].append(sl)
        return n

    warm = empty_chunk()
    jax.block_until_ready(prog(*warm))
    del warm
    t_plan = time.perf_counter() - t0
    prefetch = config.knob("CYLON_TPU_PREFETCH")
    t_run0 = time.perf_counter()
    total = 0
    nxt = slice_chunk(0) if prefetch else None
    for p in range(n_passes):
        cur = nxt if prefetch else slice_chunk(p)
        fut = prog(*cur)
        nxt = slice_chunk(p + 1) if prefetch and p + 1 < n_passes else None
        total += fetch_and_store(fut, p)
        del cur, fut
    del nxt
    t_run = time.perf_counter() - t_run0
    result = (None if out_dir is not None
              else [_concat_host(fs) for fs in acc])
    stats = {"passes": n_passes, "world": world, "rows": total,
             "per_target": per_target.tolist(),
             "plan_seconds": t_plan, "run_seconds": t_run,
             "total_seconds": t_plan + t_run}
    return result, stats


def chunked_unique(data, columns=None, *, passes: int = 4,
                   mode: str = "auto", ctx=None):
    """Out-of-core distinct rows over the given columns (default: all):
    a group-by with no aggregates — the key-domain partition makes every
    pass's distinct set globally disjoint (streamed analog of
    DistributedUnique's shuffle-then-local-unique, table.cpp:1031-1047).

    Returns (dict of host columns, stats with "rows")."""
    if columns is None:
        # names only — never materialize columns here; chunked_groupby
        # does the one full host conversion itself
        if isinstance(data, dict):
            columns = [str(k) for k in data]    # mirror _as_host_frame
        elif hasattr(data, "names"):            # cylon_tpu Table
            columns = list(data.names)
        else:                                   # pandas DataFrame
            columns = [str(c) for c in data.columns]
    result, stats = chunked_groupby(data, columns, {}, passes=passes,
                                    mode=mode, ctx=ctx)
    stats["rows"] = stats.pop("groups")
    return result, stats


def chunked_sort(data, by, *, ascending=True, nulls_first: bool = True,
                 passes: int = 4, ctx=None, pass_guard=None):
    """Out-of-core GLOBAL sort of one host frame: range-partition on the
    first sort column's order-preserving prefix (equal keys co-locate,
    ranges are contiguous in key order), sort each pass on device, and
    emit passes in key order — the streamed analog of DistributedSort's
    sample + range shuffle + local sort (reference table.cpp:313-356).
    Null first-key rows are routed to whichever pass is emitted first
    (``nulls_first``) or last, since the planning prefix cannot express
    the device kernels' null ordering.

    Returns (dict of host columns in global sort order, stats)."""
    t0 = time.perf_counter()
    names, arrs = _as_host_frame(data)
    by_names = _resolve_keys(names, by, None, "sort")
    if isinstance(ascending, bool):
        ascending = [ascending] * len(by_names)
    if len(ascending) != len(by_names):
        raise CylonError(Code.Invalid,
                         f"ascending length {len(ascending)} != "
                         f"{len(by_names)} sort columns")
    key0 = np.asarray(arrs[by_names[0]])
    empty = np.zeros(0, key0.dtype)
    pid, _, n_passes, _ = _plan_pass_ids([key0], [empty], passes, "range")
    emit_order = (list(range(n_passes)) if ascending[0]
                  else list(range(n_passes - 1, -1, -1)))
    nulls = _null_mask(key0)
    if nulls is not None and nulls.any():
        target = emit_order[0] if nulls_first else emit_order[-1]
        pid = np.where(nulls, target, pid)
    counts = np.bincount(pid, minlength=n_passes)
    cap = pow2ceil(int(max(8, counts.max(initial=0))))
    by_idx = tuple(names.index(n) for n in by_names)
    asc = tuple(bool(a) for a in ascending)

    world = 1 if ctx is None else ctx.GetWorldSize()
    frames: List[Dict[str, np.ndarray]] = []
    total = 0
    if world > 1:
        from .config import SortOptions
        from .table import Table

        t_plan = time.perf_counter() - t0
        t_run0 = time.perf_counter()
        for p in emit_order:
            if pass_guard is not None:
                pass_guard()
            sel = pid == p
            t = Table.from_numpy(names, [np.asarray(arrs[n])[sel]
                                         for n in names], ctx=ctx,
                                 capacity=cap)
            s = t.distributed_sort(
                by_names, options=SortOptions(nulls_first=nulls_first),
                ascending=list(asc))
            frames.append(s.to_numpy())
            total += s.row_count
    else:
        from .ops import sort as sort_mod

        build = _SideBuilder(names, arrs, pid, cap)

        @jax.jit
        def prog(cols, cnt):
            return sort_mod.sort_rows(cols, cnt, by_idx, asc, nulls_first)

        def fetch(out):
            scols, cnt = out
            n = int(cnt)
            return {name: colmod.to_numpy(c, n)
                    for name, c in zip(names, scols)}, n

        journal = None
        if durable.enabled():
            # positional passes (no refinement), keyed by emit position
            fp = durable.run_fingerprint(
                "sort",
                (tuple(by_names), tuple(asc), bool(nulls_first),
                 int(n_passes), 1),
                ((names, arrs),))
            journal = durable.open_run(fp, "sort")
        extra = {}
        t_plan, t_run0, frames, total = _run_passes(
            prog, build.empty_chunk, lambda p: build.chunk(emit_order[p]),
            n_passes, fetch, t0, stats=extra, journal=journal,
            pass_guard=pass_guard)
        if journal is not None and not extra.get("quarantined"):
            journal.record_done(len(frames), total)
            durable.gc_journal()
    result = _concat_host(frames)
    t_run = time.perf_counter() - t_run0
    stats = {"passes": n_passes, "mode": "range", "world": world,
             "rows": total, "plan_seconds": t_plan, "run_seconds": t_run,
             "total_seconds": t_plan + t_run}
    if world == 1:
        for k in ("passes_skipped", "quarantined", "retries", "parts_run"):
            if k in extra:
                stats[k] = extra[k]
    return result, stats


# ---------------------------------------------------------------------------
# legacy wrappers (the round-3 fixed-schema entry points, now thin)
# ---------------------------------------------------------------------------

def key_range_bounds(lo: int, hi: int, passes: int) -> List[Tuple[int, int]]:
    """Split [lo, hi) into ``passes`` near-equal [start, stop) intervals."""
    if passes < 1:
        raise ValueError(f"passes must be >= 1, got {passes}")
    span = hi - lo
    edges = [lo + (span * p) // passes for p in range(passes)] + [hi]
    return [(edges[p], edges[p + 1]) for p in range(passes)]


def chunked_join_groupby(lk: np.ndarray, lv: np.ndarray,
                         rk: np.ndarray, rv: np.ndarray,
                         passes: int, algo: str = "sort",
                         aggs: Tuple[Tuple[int, AggOp], ...] = (
                             (1, AggOp.SUM), (3, AggOp.MEAN))):
    """INNER join on int keys + group-by over key, in ``passes`` key-domain
    passes — the bench driver's fixed (k,v)x(k,v) shape, now a wrapper
    over the general engine.  Returns ({"key", "agg0", ...}, stats)."""
    joined = ["l_k", "a", "r_k", "b"]
    agg: Dict[str, list] = {}
    labels = []
    for idx, op in aggs:
        name = joined[idx]
        agg.setdefault(name, []).append(op)
        labels.append(f"{op.name.lower()}_{name}")
    result, stats = chunked_join_groupby_tables(
        {"k": lk, "a": lv}, {"k": rk, "b": rv}, on="k", how="inner",
        group_by="l_k", agg=agg, passes=passes, algo=algo, mode="auto")
    out = {"key": result["l_k"]}
    for i, label in enumerate(labels):
        out[f"agg{i}"] = result[label]
    return out, stats


def chunked_distributed_join_groupby(lk: np.ndarray, lv: np.ndarray,
                                     rk: np.ndarray, rv: np.ndarray,
                                     passes: int, ctx,
                                     agg: Optional[Dict] = None):
    """Multi-chip rung of the out-of-core ladder over the bench schema —
    now a wrapper over the general engine's distributed path.

    Returns (pandas-convertible dict of host arrays, stats)."""
    if agg is None:
        agg = {"a": ["sum"], "b": ["mean"]}
    return chunked_join_groupby_tables(
        {"k": lk, "a": lv}, {"k": rk, "b": rv}, on="k", how="inner",
        group_by="l_k", agg=agg, passes=passes, ctx=ctx, mode="auto")
