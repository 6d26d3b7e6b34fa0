"""Distributed operators: shuffle, join support, sort, group-by, reductions.

TPU-native replacement for the reference's L4 distributed-operator recipes
(cpp/src/cylon/table.cpp:313-1047, groupby/groupby.cpp:23-114,
compute/aggregates.cpp:30-156).  Every operator keeps the reference's
*partition -> all-to-all -> local kernel* shape, but each phase is a jit
shard_map program and the communication is XLA collectives.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes, precision
from ..column import Column
from ..config import SortOptions
from ..context import PARTITION_AXIS, CylonContext
from ..obs import metrics as obs_metrics
from ..obs import spans as obs_spans
from ..ops import aggregates as agg_mod
from ..ops import groupby as groupby_mod
from ..ops import sort as sort_mod
from ..ops.groupby import AggOp
from ..status import Code, CylonError
from . import collectives
from . import partition as partition_mod
from . import plane as plane_mod
from . import shuffle as shuffle_mod



def _shard_map(ctx: CylonContext, fn, key: tuple, shapes_key: tuple,
               out_specs=None):
    from jax.sharding import PartitionSpec as P

    from .. import config
    from ..context import ctx_cache
    from ..utils import shard_map

    cache = ctx_cache(ctx, "_plan_cache")
    # every trace-scope knob participates in every plan key: flipping e.g.
    # CYLON_TPU_SHUFFLE_PACK or CYLON_TPU_ACCUM must retrace, never serve
    # a program traced under the other realization (the PR 2 bug class,
    # generalized; cylint rule CY103 treats builders that append this token
    # as key-complete).  How the local kernels are realized is not in the
    # key: it follows the platform (ops/realization.py) and cannot change
    # in a process
    cache_key = (key, shapes_key, config.trace_cache_token())
    entry = cache.get(cache_key)
    if entry is None:
        obs_metrics.counter_add("plan_cache.miss")
        spec = P(PARTITION_AXIS)
        entry = jax.jit(shard_map(
            fn, mesh=ctx.mesh, in_specs=spec,
            out_specs=spec if out_specs is None else out_specs,
            check_vma=False))
        cache[cache_key] = entry
    else:
        obs_metrics.counter_add("plan_cache.hit")
    return entry


def _shapes_key(t) -> tuple:
    # names are static metadata baked into shard-fn closures, so they must
    # key the cache alongside shapes/dtypes
    return (t.capacity, t.names,
            tuple((c.dtype, c.data.shape[1:]) for c in t.columns))


# ---------------------------------------------------------------------------
# shuffle (reference: Shuffle, table.cpp:951-964)
# ---------------------------------------------------------------------------

def _counts_for(t, key_idx: Tuple[int, ...], mode: str,
                opts: SortOptions | None, hot: tuple = ()):
    """[world, world] count matrix for a prospective shuffle, replicated on
    every process (multi-host planners need it host-side everywhere);
    under a skew mode (``hot`` given) also ``_split_targets``' numbers."""
    from jax.sharding import PartitionSpec as P

    world = t.num_shards
    ctx = t.ctx

    def fn(tt, *hot):
        tgt, skew = _split_targets(tt, key_idx, world, mode, opts, hot)
        counts = shuffle_mod.target_counts(tgt, world)  # [world] per shard
        return (collectives.allgather(counts, axis=0).reshape(world, world),
                ) + skew

    return _shard_map(ctx, fn, ("counts", key_idx, mode, opts), _shapes_key(t),
                      out_specs=(P(),) + _skew_specs(hot))(t, *hot)


def _targets_and_counts(t, key_idx: Tuple[int, ...], mode: str,
                        opts: SortOptions | None, hot: tuple = ()):
    """One targets pass returning (sharded targets array, replicated
    [world, world] count matrix) — the exchange program reuses the targets
    instead of re-hashing, and every process can size the plan."""
    from jax.sharding import PartitionSpec as P

    world = t.num_shards
    ctx = t.ctx

    def fn(tt, *hot):
        tgt, skew = _split_targets(tt, key_idx, world, mode, opts, hot)
        counts = shuffle_mod.target_counts(tgt, world)
        return (tgt, collectives.allgather(counts, axis=0).reshape(
            world, world)) + skew

    return _shard_map(ctx, fn, ("targets+counts", key_idx, mode, opts),
                      _shapes_key(t),
                      out_specs=(P(PARTITION_AXIS), P()) + _skew_specs(hot))(
                          t, *hot)


def _targets_counts_stats(t, key_idx: Tuple[int, ...], mode: str,
                          opts: SortOptions | None, hot: tuple = ()):
    """The compression pre-pass: ONE program returning (sharded targets,
    replicated count matrix, replicated per-column value stats).  The
    stats ride the pass that already touches every key (the count-matrix
    pass), reduced with allreduce collectives so every process derives
    the identical compression spec from them (plane.build_spec)."""
    from jax.sharding import PartitionSpec as P

    world = t.num_shards
    ctx = t.ctx
    n_stats = partition_mod.stats_arity(t.columns)

    def fn(tt, *hot):
        tgt, skew = _split_targets(tt, key_idx, world, mode, opts, hot)
        counts = shuffle_mod.target_counts(tgt, world)
        cm = collectives.allgather(counts, axis=0).reshape(world, world)
        stats = partition_mod.column_stats(tt.columns, tt.row_counts[0])
        return (tgt, cm, stats) + skew

    return _shard_map(ctx, fn, ("targets+counts+stats", key_idx, mode, opts),
                      _shapes_key(t),
                      out_specs=(P(PARTITION_AXIS), P(),
                                 tuple(P() for _ in range(n_stats)))
                      + _skew_specs(hot))(t, *hot)


def _counts_stats_for(t, key_idx: Tuple[int, ...], mode: str,
                      opts: SortOptions | None, hot: tuple = ()):
    """Bucketed-path compression pre-pass: replicated (count matrix,
    stats) — _counts_for plus the observation, with NO sharded targets
    output (the bucketed exchange recomputes targets inside its own
    program, so materializing them here would be pure waste)."""
    from jax.sharding import PartitionSpec as P

    world = t.num_shards
    ctx = t.ctx
    n_stats = partition_mod.stats_arity(t.columns)

    def fn(tt, *hot):
        tgt, skew = _split_targets(tt, key_idx, world, mode, opts, hot)
        counts = shuffle_mod.target_counts(tgt, world)
        cm = collectives.allgather(counts, axis=0).reshape(world, world)
        return (cm, partition_mod.column_stats(tt.columns, tt.row_counts[0])
                ) + skew

    return _shard_map(ctx, fn, ("counts+stats", key_idx, mode, opts),
                      _shapes_key(t),
                      out_specs=(P(), tuple(P() for _ in range(n_stats)))
                      + _skew_specs(hot))(t, *hot)


def _skew_specs(hot: tuple) -> tuple:
    """The out_specs of ``_split_targets``' replicated numbers: one where
    the program runs a skew mode (``hot`` given), none otherwise."""
    from jax.sharding import PartitionSpec as P

    return (P(),) if hot else ()


def _split_targets(tt, key_idx, world, mode, opts, hot: tuple):
    """``(targets, numbers)``: ``_targets``, and under a skew mode the
    replicated ``int32[2]`` (hot keys, the rows of every shard whose key
    is hot) that ride to the host with the count matrix; ``()`` in a plain
    mode."""
    if mode not in SKEW_MODES:
        return _targets(tt, key_idx, world, mode, opts), ()
    with obs_spans.span("shuffle.partition", mode=mode, world=world):
        tgt, hot_rows = _skew_targets(tt, key_idx, world, mode, *hot)
    n = hot[1][0]
    return tgt, (jnp.stack([n, collectives.allreduce_sum(hot_rows)]),)


def _targets(tt, key_idx, world, mode, opts: SortOptions | None):
    # the span fires at TRACE time (this runs under shard_map tracing):
    # it nests the partition phase under the enclosing plan/exchange span
    # on plan-cache misses and never reads a tracer (cylint CY101)
    with obs_spans.span("shuffle.partition", mode=mode, world=world):
        count = tt.row_counts[0]
        if mode == "hash":
            return partition_mod.hash_targets(tt.columns, count, key_idx,
                                              world)
        assert mode == "range"
        return partition_mod.range_targets(
            tt.columns[key_idx[0]], count, world,
            num_bins=opts.num_bins or 16 * world,
            num_samples=opts.num_samples or 4096,
            ascending=opts.ascending, nulls_first=opts.nulls_first)


# ---------------------------------------------------------------------------
# the skew split of a join's exchange: partial redistribution, partial
# duplication (Xu et al., "Handling data skew in parallel joins in
# shared-nothing systems", SIGMOD 2008).  Probe (left) rows whose key is
# hot stay on their shard; build (right) rows whose key is hot go to every
# shard; every other row is hash-exchanged.  Every shape below is set in
# code, none by the data: the hot set is an operand of the programs.
# ---------------------------------------------------------------------------

#: probe rows sampled a shard, by a stride over its live rows (a shard
#: with fewer live rows than the fullest samples proportionally fewer)
SKEW_SAMPLE = 4096
#: capacity of the hot set (K)
SKEW_HOT_KEYS = 64
#: a key is hot when more than this share of the samples holds it
#: (``> samples / SKEW_SHARE_INV``: 16 of 16,384 on four shards)
SKEW_SHARE_INV = 1024
#: rows a shard may hold back for every shard (R): a hot key stays hot
#: only while the build rows of the hot keys, over all shards, fit it
SKEW_BLOCK_ROWS = 1024
#: the probe side's and the build side's targeting modes
SKEW_MODES = ("hash.keep", "hash.spread")


def _member(h: jax.Array, hot: jax.Array, n) -> jax.Array:
    """bool: ``h`` is in the hot set.  ``hot`` holds its ``n`` hashes
    sorted, the slots past them a copy of the first: every slot is
    compared, which XLA:TPU fuses into one reduction over ``h`` with no
    gather and no ``[rows, K]`` buffer."""
    return jnp.any(h[:, None] == hot[None, :], axis=1) & (n > 0)


def _hot_keys(lcols, lcount, lkeys, rcols, rcount, rkeys, world: int):
    """``(hot, n)``, the same on every shard: up to ``SKEW_HOT_KEYS``
    hashes of probe keys that more than 1/``SKEW_SHARE_INV`` of a stride
    sample of the probe rows holds, most sampled first, and their count.
    A key is dropped again, fewest build rows kept first, while the build
    rows of the hot keys over all shards would pass ``SKEW_BLOCK_ROWS``:
    such a key takes the plain hash exchange on both sides.  Runs under
    shard_map; the samples and the build counts are gathered and summed
    over the shards, so every shard derives the same set."""
    S, K, R = SKEW_SAMPLE, SKEW_HOT_KEYS, SKEW_BLOCK_ROWS
    fullest = collectives.allreduce_max(lcount.astype(jnp.int32))
    # this shard's samples, a stride over its live rows
    n_s = (S * lcount.astype(jnp.float32)
           / jnp.maximum(fullest, 1).astype(jnp.float32))
    n_s = jnp.ceil(n_s).astype(jnp.int32)
    j = jnp.arange(S, dtype=jnp.int32)
    pos = ((j.astype(jnp.float32) + 0.5) * lcount.astype(jnp.float32)
           / jnp.maximum(n_s, 1).astype(jnp.float32)).astype(jnp.int32)
    pos = jnp.clip(pos, 0, jnp.maximum(lcount - 1, 0))
    sample = [lcols[i].take(pos) for i in lkeys]
    h, _ = partition_mod.key_hashes(sample, range(len(sample)), world)
    late = collectives.allgather((j >= n_s).astype(jnp.int32)).reshape(-1)
    h = collectives.allgather(h).reshape(-1)
    # runs of equal hashes, the samples past a shard's count last
    late, h = jax.lax.sort((late, h), num_keys=2)
    i = jnp.arange(h.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (h[1:] != h[:-1]) | (late[1:] != late[:-1])])
    last = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    start = jax.lax.cummax(jnp.where(first, i, 0))
    run = jnp.where(last & (late == 0), i - start + 1, 0)
    samples = jnp.sum(1 - late)
    run = jnp.where(run * SKEW_SHARE_INV > samples, run, 0)
    top, at = jax.lax.top_k(run, K)
    cand = jnp.take(h, at)
    valid = top > 0
    # build rows of each candidate, over all shards
    rh, _ = partition_mod.key_hashes(rcols, rkeys, world)
    rlive = jnp.arange(rh.shape[0], dtype=jnp.int32) < rcount
    built = jnp.sum((rh[:, None] == cand[None, :]) & rlive[:, None], axis=0,
                    dtype=jnp.int32)
    built = collectives.allreduce_sum(jnp.where(valid, built, 0))
    # fewest build rows first (the most sampled first among equals)
    key = jnp.where(valid, built, jnp.iinfo(jnp.int32).max)
    _, order = jax.lax.sort((key, jnp.arange(K, dtype=jnp.int32)),
                            num_keys=2)
    fits = jnp.take(valid, order) & (
        jnp.cumsum(jnp.take(built, order)) <= R)
    keep = jnp.zeros((K,), bool).at[order].set(fits)
    n = jnp.sum(keep, dtype=jnp.int32)
    hot = jnp.sort(jnp.where(keep, cand, jnp.uint32(0xFFFFFFFF)))
    hot = jnp.where(jnp.arange(K) < n, hot, hot[0])
    return hot, n


def _skew_targets(tt, key_idx, world: int, mode: str, hot, n):
    """(targets, this shard's live rows whose key is hot) of one side of
    the skew split: a hot probe row targets its own shard
    (``hash.keep``); a hot build row targets padding (``hash.spread``),
    which the exchange holds back and sends to every shard
    (``shuffle._append_held``); every other live row its hash target."""
    from ..ops import compact as compact_mod

    cap = tt.columns[0].data.shape[0]
    h, t = partition_mod.key_hashes(tt.columns, key_idx, world)
    live = compact_mod.live_mask(cap, tt.row_counts[0])
    is_hot = _member(h, hot, n[0]) & live
    keep = collectives.my_rank() if mode == "hash.keep" else world
    t = jnp.where(is_hot, jnp.asarray(keep, jnp.int32), t)
    return (jnp.where(live, t, jnp.int32(world)),
            jnp.sum(is_hot, dtype=jnp.int32))


def _hot_set(left, right, lkeys: Tuple[int, ...], rkeys: Tuple[int, ...]):
    """The program ``skew_fn``: every shard's copy of the hot set, as
    ``(uint32[world * K], int32[world])`` sharded one copy a shard, the
    operand of both sides' targets passes.  No host sync."""
    world = left.num_shards

    def skew_fn(lt, rt):
        hot, n = _hot_keys(lt.columns, lt.row_counts[0], lkeys, rt.columns,
                           rt.row_counts[0], rkeys, world)
        return hot, jnp.reshape(n, (1,))

    return _shard_map(left.ctx, skew_fn, ("skew", lkeys, rkeys),
                      (_shapes_key(left), _shapes_key(right)))(left, right)


def join_exchange(left, right, left_on: Sequence[int],
                  right_on: Sequence[int], split: bool):
    """Both sides of a distributed join, exchanged so that every pair of
    rows with equal keys meets on one shard exactly once: ``(left, right,
    hot keys)``.

    ``split`` (an INNER join, or a LEFT join that keeps the left side):
    the skew split.  ``skew_fn`` finds the hot set on the device; the
    left rows whose key is hot stay on their shard, the right rows whose
    key is hot are copied to every shard, and every other row is
    hash-exchanged.  The hot count reaches the host with the count
    matrices the exchanges fetch anyway.  Unsplit, or with no key hot,
    both sides end hash-partitioned on their keys."""
    left_on, right_on = tuple(left_on), tuple(right_on)
    if not split:
        return shuffle(left, left_on), shuffle(right, right_on), 0
    with obs_spans.span("shuffle.skew", world=left.num_shards) as sp:
        hot = _hot_set(left, right, left_on, right_on)
        kept, held = {}, {}
        left_sh = _shuffled(left, left_on, "hash.keep", hot=hot, note=kept)
        right_sh = _shuffled(right, right_on, "hash.spread", hot=hot,
                             note=held)
        n = kept.get("hot_keys", 0)
        sp.set(hot_keys=n)
    obs_metrics.counter_add("join.skew.hot_keys", n)
    obs_metrics.counter_add("join.skew.kept_rows", kept.get("hot_rows", 0))
    obs_metrics.counter_add("join.skew.replicated_rows",
                            held.get("hot_rows", 0) * left.num_shards)
    return left_sh, right_sh, n


def _probe_ragged(ctx) -> bool:
    """One tiny RaggedAllToAll program on the context's mesh: each rank
    sends one element to every rank.  XLA:CPU does not implement the
    collective, so there the shuffle is bucketed and nothing is probed.
    Every other backend must compile and run it: a failure raises with the
    compiler's message instead of quietly hiding the device path."""
    from jax.sharding import PartitionSpec as P

    if jax.default_backend() == "cpu":
        return False
    world = ctx.GetWorldSize()

    def fn(x):
        me = jax.lax.axis_index(PARTITION_AXIS)
        out = jnp.zeros((world,), jnp.int32)
        io = jnp.arange(world, dtype=jnp.int32)
        ones = jnp.ones((world,), jnp.int32)
        oo = jnp.full((world,), me, jnp.int32)
        return jax.lax.ragged_all_to_all(x, out, io, ones, oo, ones,
                                         axis_name=PARTITION_AXIS)

    from ..utils import shard_map

    f = jax.jit(shard_map(fn, mesh=ctx.mesh, in_specs=P(PARTITION_AXIS),
                          out_specs=P(PARTITION_AXIS), check_vma=False))
    jax.block_until_ready(f(jnp.zeros((world * world,), jnp.int32)))
    return True


def _ragged_enabled(ctx) -> bool:
    """Capability check, cached PER CONTEXT: a process that touches a
    CPU-mesh context first (probe -> False) and later a TPU context must
    re-probe on the TPU mesh, not inherit the CPU verdict."""
    from .. import config
    from ..context import ctx_cache

    env = config.knob("CYLON_TPU_SHUFFLE")
    if env == "bucketed":
        return False
    cache = ctx_cache(ctx, "_ragged_probe")
    if "ragged" not in cache:
        cache["ragged"] = _probe_ragged(ctx)
    if env == "ragged" and not cache["ragged"]:
        raise RuntimeError(
            "CYLON_TPU_SHUFFLE=ragged requested but this backend does not "
            "implement RaggedAllToAll")
    return cache["ragged"]


def _row_bytes(cols, packed: bool, spec=None) -> int:
    """Exchanged bytes per row under either realization — plane words when
    packed (compressed plane words under ``spec``), data+validity+lengths
    buffer bytes per-buffer (all static shape/dtype metadata, host-side)."""
    if packed:
        return plane_mod.plane_words(cols, spec) * 4
    total = 0
    for c in cols:
        total += c.data.dtype.itemsize * int(
            math.prod(c.data.shape[1:])) + 1  # data row + 1 validity byte
        if c.lengths is not None:
            total += c.lengths.dtype.itemsize
    return total


def _record_exchange(cols, packed: bool, family: str,
                     rows_exchanged: int, spec=None, rounds: int = 1,
                     operand_rows=None) -> None:
    """Account one collective exchange that actually ran: data-collective
    launch count (1 packed vs one per buffer — the PR-3 budget goldens'
    1-vs-13 on the canonical 6-column frame), the counts all_gather, and
    global bytes moved.  Under a compression spec, ``shuffle.bytes_sent``
    records the bytes that really traveled; the uncompressed-minus-sent
    delta lands in ``shuffle.bytes_saved`` and the per-exchange ratio in
    the ``shuffle.compress_ratio`` gauge.  ``shuffle.rounds`` counts the
    rounds the exchange went in and ``shuffle.operand_bytes`` the bytes of
    its send operands in the layout the collective moves: the ragged
    family's ``operand_rows`` at one 128-lane vector a row, the bucketed
    family's padded buckets, which are the bytes sent.  Of a packed
    plane's words, ``shuffle.payload_lanes`` counts those that rode the
    ragged exchange's target sort (``shuffle.riding_words``) and
    ``shuffle.take_lanes`` those taken through a permutation."""
    launches = 1 if packed else shuffle_mod.buffer_count(cols)
    bytes_sent = rows_exchanged * _row_bytes(cols, packed, spec)
    if packed:
        words = plane_mod.plane_words(cols, spec)
        ride = shuffle_mod.riding_words(words) if family == "ragged" else 0
        obs_metrics.counter_add("shuffle.payload_lanes", ride)
        obs_metrics.counter_add("shuffle.take_lanes", words - ride)
    obs_metrics.counter_add("shuffle.exchanges")
    obs_metrics.counter_add("shuffle.collective_launches", launches)
    obs_metrics.counter_add("shuffle.counts_gathers")
    obs_metrics.counter_add("shuffle.bytes_sent", bytes_sent)
    obs_metrics.counter_add("shuffle.rounds", rounds)
    obs_metrics.counter_add(
        "shuffle.operand_bytes",
        bytes_sent if operand_rows is None
        else operand_rows * launches * shuffle_mod.RAGGED_ROW_BYTES)
    if spec is not None:
        raw_bytes = rows_exchanged * _row_bytes(cols, packed)
        obs_metrics.counter_add("shuffle.bytes_saved",
                                max(0, raw_bytes - bytes_sent))
        if bytes_sent > 0:
            obs_metrics.gauge_set("shuffle.compress_ratio",
                                  raw_bytes / bytes_sent)
    # distribution, not just the total: one hot exchange in a hundred
    # small ones is invisible in the counter but not in the histogram
    obs_metrics.hist_observe("shuffle.bytes_per_exchange", bytes_sent)
    obs_spans.instant("shuffle.exchange_done", family=family, packed=packed,
                      compressed=spec is not None,
                      collective_launches=launches, rows=rows_exchanged)


def _record_broadcast(cols, packed: bool, world: int, rows_buf: int) -> None:
    """Account one broadcast replication (static shape metadata only, no
    device sync).  Deliberately NOT ``shuffle.exchanges`` — tests pin
    exchange counts per plan shape, and a broadcast is the strategy that
    AVOIDED an exchange; it gets its own counter."""
    launches = 1 if packed else 1 + shuffle_mod.buffer_count(cols)
    bytes_sent = rows_buf * world * _row_bytes(cols, packed)
    obs_metrics.counter_add("shuffle.broadcasts")
    obs_metrics.counter_add("shuffle.collective_launches", launches)
    obs_metrics.counter_add("shuffle.bytes_sent", bytes_sent)
    obs_metrics.hist_observe("shuffle.bytes_per_exchange", bytes_sent)
    obs_spans.instant("shuffle.broadcast_done", packed=packed,
                      collective_launches=launches,
                      rows=rows_buf * world)


def broadcast_gather(t):
    """Replicate a (small) distributed table onto every shard — the
    broadcast-hash join's build side.

    Packed path runs exactly ONE all_gather: the shard's rows pack into
    the bit-plane, one extra meta row carries the live-row count in
    word 0 (a counts all_gather would be a second launch — the budget
    goldens pin broadcast joins at 1 gather), and every shard unpacks
    the [world, cap+1, words] result, compacting live rows front-wise
    in source-rank order.  The per-buffer fallback (packing disabled)
    gathers counts plus each buffer.  No compression: the build side is
    dimension-sized by the cost model's admission, so spec estimation
    overhead cannot pay for itself.

    The result is replicated (same rows, same order, every shard) and
    feeds the collective-free local join probe; it never escapes the
    executor."""
    from .. import resilience
    from ..ops import compact as compact_mod
    from ..table import Table

    world = t.num_shards
    if world == 1:
        return t
    ctx = t.ctx
    names = t.names
    cap = t.shard_capacity
    out_cap = cap * world
    pack = plane_mod.pack_enabled()

    def gather():
        resilience.fault_point("broadcast")
        if pack:
            def bcfn(tt):
                plane = plane_mod.pack_plane(tt.columns)
                meta = jnp.zeros((1, plane.shape[1]), dtype=plane.dtype)
                meta = meta.at[0, 0].set(
                    tt.row_counts[0].astype(plane.dtype))
                g = collectives.allgather(
                    jnp.concatenate([plane, meta], axis=0), axis=0)
                counts = g[:, cap, 0].astype(jnp.int32)
                rows = g[:, :cap, :].reshape(world * cap, -1)
                live = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                        < counts[:, None]).reshape(world * cap)
                perm, m = compact_mod.compact_indices(live)
                valid = jnp.arange(out_cap, dtype=jnp.int32) < m
                cols = plane_mod.unpack_plane(
                    jnp.take(rows, perm, axis=0, mode="clip"),
                    tt.columns, valid_mask=valid)
                return Table(cols, jnp.reshape(m, (1,)), names, ctx)
        else:
            def bcfn(tt):
                counts = collectives.allgather(
                    tt.row_counts, axis=0).reshape(world).astype(jnp.int32)
                live = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                        < counts[:, None]).reshape(world * cap)
                perm, m = compact_mod.compact_indices(live)
                valid = jnp.arange(out_cap, dtype=jnp.int32) < m
                cols = []
                for c in tt.columns:
                    gd = collectives.allgather(c.data, axis=0).reshape(
                        (world * cap,) + c.data.shape[1:])
                    gv = collectives.allgather(c.validity, axis=0).reshape(
                        world * cap)
                    gl = None
                    if c.lengths is not None:
                        gl = collectives.allgather(
                            c.lengths, axis=0).reshape(world * cap)
                    cols.append(Column(gd, gv, gl, c.dtype).take(
                        perm, valid_mask=valid))
                return Table(tuple(cols), jnp.reshape(m, (1,)), names, ctx)

        with obs_spans.span("shuffle.broadcast", packed=pack, world=world):
            out = _shard_map(ctx, bcfn, ("bcast", pack, out_cap),
                             _shapes_key(t))(t)
        _record_broadcast(t.columns, pack, world, cap + 1 if pack else cap)
        return out

    out, _attempts = resilience.retry_call(
        gather, policy=ctx.collective_retry_policy(), site="broadcast")
    return out


def _shuffled(t, key_idx: Tuple[int, ...], mode: str = "hash",
              opts: SortOptions | None = None, hot: tuple = (),
              note: dict | None = None):
    """partition -> all-to-all -> compact; returns a new distributed Table.

    The exchange prefers the skew-proof RaggedAllToAll path (exact traffic,
    no bucket padding, targets computed once); if the active backend lacks
    the ragged collective the bucketed path is used and remembered.

    A skew mode (``SKEW_MODES``) takes the hot set ``hot`` of ``_hot_set``
    and writes into ``note`` the host's copy of its numbers, fetched with
    the count matrix: ``hot_keys`` and ``hot_rows`` (kept or held back,
    over all shards).  Under ``hash.spread`` every shard receives
    ``SKEW_BLOCK_ROWS`` rows a shard besides the matrix's.
    """
    from .. import resilience
    from ..table import Table, host_sync

    world = t.num_shards
    ctx = t.ctx
    names = t.names

    def exchange():
        # the named injection site for the collective exchange; a real or
        # injected transient failure retries the WHOLE plan+exchange (the
        # input table is untouched, so the retry is exact)
        resilience.fault_point("shuffle")
        # phase timers mirror the reference's split/shuffle chrono spans
        # (partition/partition.cpp:29-57, table.cpp:163-175)
        # the packed-plane knob is read at trace time, so it must key the
        # plan cache — flipping CYLON_TPU_SHUFFLE_PACK can never serve a
        # program traced under the other realization
        pack = plane_mod.pack_enabled()
        # compression rides the packed plane: the pre-pass additionally
        # observes per-column value stats (replicated via allreduce) and
        # the host folds them into the static spec.  The spec is realized
        # -data-derived jit layout, so it rides the exchange plan cache
        # key below (cylint CY109) — a data change retraces, never
        # decodes under a stale layout.
        compress = pack and plane_mod.compress_enabled()
        held = SKEW_BLOCK_ROWS if mode == "hash.spread" else 0

        def planned(fetched):
            """The count matrix on the host, and the skew numbers noted."""
            cm, *skew = fetched
            if skew:
                hot_keys, hot_rows = (int(v) for v in np.asarray(skew[0]))
                if held and hot_rows > held:
                    raise CylonError(
                        Code.CapacityError,
                        f"{hot_rows} build rows of hot keys held back, "
                        f"more than the {held} a block holds")
                if note is not None:
                    note.update(hot_keys=hot_keys, hot_rows=hot_rows)
            return np.asarray(cm).reshape(world, world)

        if _ragged_enabled(ctx):
            with obs_spans.span("shuffle.plan", mode=mode, world=world,
                      family="ragged"):
                # sized here, inside the retried exchange — the task-graph
                # path also calls plan_shuffle, so the injection site
                # lives with the recovery wrapper, not the sizing math
                resilience.fault_point("shuffle_plan")
                spec = None
                if compress:
                    targets, counts, stats, *skew = _targets_counts_stats(
                        t, key_idx, mode, opts, hot)
                    spec = plane_mod.build_spec(
                        t.columns, [np.asarray(s) for s in
                                    host_sync(stats, "shuffle.stats")], world,
                        t.shard_capacity)
                else:
                    targets, counts, *skew = _targets_and_counts(
                        t, key_idx, mode, opts, hot)
                cm = planned(host_sync((counts, *skew), "shuffle.plan"))
                _, out_cap = shuffle_mod.plan_shuffle(cm, held * world)
                # the rounds of a shard over the collective's operand
                # limit, sized from the count matrix already here
                rounds, operand_rows = shuffle_mod.plan_rounds(
                    cm, t.shard_capacity)

            def rfn(tt, tgt):
                cols, total = shuffle_mod.shuffle_shard_ragged(
                    tt.columns, tgt, world, out_cap, spec=spec,
                    rounds=rounds, held_rows=held, count=tt.row_counts[0])
                return Table(cols, jnp.reshape(total, (1,)), names, ctx)

            with obs_spans.span("shuffle.exchange", packed=pack, family="ragged",
                      world=world, compressed=spec is not None,
                      rounds=rounds):
                out = _shard_map(ctx, rfn,
                                 ("shuffle-ragged", key_idx, out_cap, pack,
                                  spec, rounds, held),
                                 _shapes_key(t))(t, targets)
            # ragged moves exactly the rows that exist
            _record_exchange(t.columns, pack, "ragged", int(cm.sum()),
                             spec=spec, rounds=rounds,
                             operand_rows=world * operand_rows)
            return out

        with obs_spans.span("shuffle.plan", mode=mode, world=world, family="bucketed"):
            resilience.fault_point("shuffle_plan")
            spec = None
            if compress:
                counts, stats, *skew = _counts_stats_for(t, key_idx, mode,
                                                         opts, hot)
                spec = plane_mod.build_spec(
                    t.columns, [np.asarray(s) for s in
                                host_sync(stats, "shuffle.stats")], world,
                    t.shard_capacity)
            else:
                counts, *skew = _counts_for(t, key_idx, mode, opts, hot)
            bucket, out_cap = shuffle_mod.plan_shuffle(
                planned(host_sync((counts, *skew), "shuffle.plan")),
                held * world)

        # unique closure name: cylint resolves closures module-wide by
        # bare name, and CY109 must see THIS body's spec use, not some
        # other `fn`'s
        def bfn(tt, *hot):
            tgt, _ = _split_targets(tt, key_idx, world, mode, opts, hot)
            cols, total = shuffle_mod.shuffle_shard(
                tt.columns, tt.row_counts[0], tgt, world, bucket, out_cap,
                spec=spec, held_rows=held)
            return Table(cols, jnp.reshape(total, (1,)), names, ctx)

        with obs_spans.span("shuffle.exchange", packed=pack, family="bucketed",
                  world=world, bucket=bucket, compressed=spec is not None):
            out = _shard_map(ctx, bfn,
                             ("shuffle", key_idx, mode, opts, bucket,
                              out_cap, pack, spec),
                             _shapes_key(t))(t, *hot)
        # every (src, dst) pair pads to the static bucket
        _record_exchange(t.columns, pack, "bucketed",
                         world * world * bucket, spec=spec)
        return out

    out, _attempts = resilience.retry_call(
        exchange, policy=ctx.collective_retry_policy(), site="shuffle")
    return out


def shuffle(t, key_idx: Tuple[int, ...]):
    """Hash-repartition rows so equal keys land on the same shard.

    The result is stamped with its partitioning property
    (``_partitioning = ("hash", ((key names,),), world)``) — the
    planner (cylon_tpu.plan) treats partitioning as tracked data
    state, so a downstream join/group-by on compatible keys can elide
    its own exchange entirely."""
    key_idx = tuple(key_idx)
    out = _shuffled(t, key_idx, "hash")
    out._partitioning = ("hash", (tuple(t.names[i] for i in key_idx),),
                         t.num_shards)
    return out


def hash_partition(t, key_idx: Tuple[int, ...], num_partitions: int):
    """Public HashPartition (reference: table.cpp:358-375): split rows into
    ``num_partitions`` tables by key hash.  Purely local like the reference
    (each rank/shard splits its own rows; no exchange): partition p's table
    holds, on every shard, that shard's rows hashing to p, front-packed.
    Returns ``{partition_id: Table}``."""
    from ..ops import compact as compact_mod
    from ..table import Table, _shard_wise

    ctx = t.ctx
    names = t.names
    key_idx = tuple(key_idx)

    from jax.sharding import PartitionSpec as P

    from ..utils import pow2ceil

    nshards = t.num_shards
    one_shard = nshards == 1
    if one_shard:
        targets = partition_mod.hash_targets(t.columns, t.row_counts[0],
                                             key_idx, num_partitions)
        counts = shuffle_mod.target_counts(targets, num_partitions)
    else:
        def cfn(tt):
            tgt = partition_mod.hash_targets(tt.columns, tt.row_counts[0],
                                             key_idx, num_partitions)
            cnts = shuffle_mod.target_counts(tgt, num_partitions)
            return tgt, collectives.allgather(cnts, axis=0).reshape(
                nshards, num_partitions)

        targets, counts = _shard_map(ctx, cfn,
                                     ("hp_counts", key_idx, num_partitions),
                                     _shapes_key(t),
                                     out_specs=(P(PARTITION_AXIS), P()))(t)
    cm = np.asarray(counts).reshape(nshards, num_partitions)
    caps = tuple(min(pow2ceil(c), t.shard_capacity) for c in cm.max(axis=0))

    # under the packed-exchange knob the per-partition compaction gathers
    # run once on the bit-packed plane (num_partitions gathers total)
    # instead of once per column per partition — same machinery as the
    # shuffle exchange, minus the collective (this op is purely local)
    pack = plane_mod.pack_enabled()

    def pfn(tt, tgt):
        packed = plane_mod.pack_plane(tt.columns) if pack else None
        outs = []
        for p in range(num_partitions):
            perm, m = compact_mod.compact_indices(tgt == p)
            idx = perm[: caps[p]]
            valid = jnp.arange(caps[p], dtype=jnp.int32) < m
            if pack:
                cols = plane_mod.unpack_plane(
                    jnp.take(packed, idx, axis=0, mode="clip"),
                    tt.columns, valid_mask=valid)
            else:
                cols = tuple(c.take(idx, valid_mask=valid)
                             for c in tt.columns)
            outs.append(Table(cols, jnp.reshape(m, (1,)), names, ctx))
        return tuple(outs)

    if one_shard:
        parts = pfn(t, targets)
    else:
        parts = _shard_map(ctx, pfn,
                           ("hash_partition", key_idx, num_partitions, caps,
                            pack),
                           _shapes_key(t))(t, targets)
    return {p: parts[p] for p in range(num_partitions)}


# ---------------------------------------------------------------------------
# distributed sort (reference: DistributedSort, table.cpp:313-356)
# ---------------------------------------------------------------------------

def distributed_sort(t, by_idx: Tuple[int, ...], opts: SortOptions,
                     asc: Tuple[bool, ...] | None = None):
    # string lead columns range-partition on their 4-byte prefix (beyond
    # the reference, whose RangePartitionKernel is numeric only)
    shuffled = _shuffled(t, tuple(by_idx), "range", opts)
    if asc is None:
        asc = tuple([opts.ascending] * len(by_idx))
    from ..table import Table

    names, ctx = t.names, t.ctx

    def fn(tt):
        cols, count = sort_mod.sort_rows(tt.columns, tt.row_counts[0],
                                         tuple(by_idx), asc, opts.nulls_first)
        return Table(cols, tt.row_counts, names, ctx)

    return _shard_map(ctx, fn, ("dsort", tuple(by_idx), asc, opts.nulls_first),
                      _shapes_key(shuffled))(shuffled)


# ---------------------------------------------------------------------------
# distributed group-by (reference: DistributedHashGroupBy,
# groupby/groupby.cpp:23-73 — partial agg, shuffle, final agg)
# ---------------------------------------------------------------------------

def groupby_partial_plan(aggs):
    """Expand requested aggs into the deduped partial-op list and its
    index: ``(partial_list, partial_index)`` where ``partial_list`` is
    ``[(src_col, partial_op), ...]`` and ``partial_index[(src, pop)]``
    is that partial's position.  ``aggs`` entries may name columns by
    index or by name — the caller's namespace is preserved.  Shared by
    the distributed two-phase group-by and the planner's fused
    join→aggregate shard body (plan/executor.py), so the two can never
    disagree on the partial layout."""
    partial_list: list = []
    partial_index: Dict[tuple, int] = {}
    for ci, op in aggs:
        for pop in groupby_mod.partial_ops(op):
            k = (ci, pop)
            if k not in partial_index:
                partial_index[k] = len(partial_list)
                partial_list.append(k)
    return partial_list, partial_index


def finalize_groupby_columns(fcols, nkeys: int, aggs, partial_index,
                             ddof: int):
    """Combine-phase outputs -> the requested agg columns: pass-through
    for SUM/MIN/MAX/COUNT, derived math for MEAN/VAR/STDDEV.  Pure jnp
    on the combined columns, so it runs identically on host-side global
    arrays (distributed_groupby step 5) and INSIDE a traced shard body
    (the planner's fused local aggregate) — bit-identity between the
    eager and fused paths rests on this being single-sourced."""
    out_cols = list(fcols[:nkeys])
    for ci, op in aggs:
        def pcol(pop, _ci=ci):
            return fcols[nkeys + partial_index[(_ci, pop)]]

        facc = precision.float_acc()
        fdt = dtypes.float_ if precision.narrow() else dtypes.double
        if op in (AggOp.SUM, AggOp.MIN, AggOp.MAX, AggOp.COUNT,
                  AggOp.SUMSQ, AggOp.COUNTSUM):
            out_cols.append(pcol(op))
        elif op == AggOp.MEAN:
            s, c = pcol(AggOp.SUM), pcol(AggOp.COUNT)
            cnt = jnp.maximum(c.data, 1).astype(facc)
            v = s.data.astype(facc) / cnt
            valid = s.validity & (c.data > 0)
            out_cols.append(Column(jnp.where(valid, v, 0.0), valid, None,
                                   fdt))
        elif op in (AggOp.VAR, AggOp.STDDEV):
            s, c, s2 = pcol(AggOp.SUM), pcol(AggOp.COUNT), pcol(AggOp.SUMSQ)
            n = jnp.maximum(c.data, 1).astype(facc)
            var = (s2.data - s.data.astype(facc) ** 2 / n) / jnp.maximum(
                n - ddof, 1.0)
            var = jnp.maximum(var, 0.0)
            if op == AggOp.STDDEV:
                var = jnp.sqrt(var)
            valid = s.validity & ((c.data - ddof) > 0)
            out_cols.append(Column(jnp.where(valid, var, 0.0), valid, None,
                                   fdt))
        else:
            raise NotImplementedError(op)
    return out_cols


def distributed_groupby(t, by_idx: Tuple[int, ...],
                        aggs: Tuple[Tuple[int, AggOp], ...], ddof: int,
                        pipeline: bool = False,
                        pre_partitioned: bool = False,
                        salt: int = 0):
    """Two-phase distributed group-by.

    ``pipeline=False`` — the reference's DistributedHashGroupBy
    (groupby/groupby.cpp:23-73): local partial aggregate, shuffle partials
    on the keys, final combine.
    ``pipeline=True`` — DistributedPipelineGroupBy (groupby/groupby.cpp:
    75-114): the local phases run the boundary-scan pipeline group-by over
    key-sorted rows; after the shuffle each shard sorts its received
    partials before the final pipeline pass (the reference's local Sort at
    groupby.cpp:103-107).

    ``pre_partitioned=True`` — the planner's shuffle elision: the caller
    proves the input is already hash-partitioned on a subset of the
    group keys (every group fully on one shard), so the partial shuffle
    is SKIPPED and the final combine folds each group's single partial
    locally — bit-identical to the shuffled path, because combining one
    partial is the identity for every combine op.

    ``salt > 1`` — the adaptive planner's skew-salted repartition,
    valid ONLY for the all-NUNIQUE single-distinct-column shape (it
    raises otherwise): instead of co-locating each group entirely on
    ``hash(keys)``'s rank (one zipfian-hot key = one overloaded rank),
    rows spread over ``hash(keys, value_bucket)`` where ``value_bucket
    = hash(value) % salt``.  Exact by construction: buckets PARTITION
    the value space, so every distinct (key, value) pair lands on
    exactly one rank, the per-rank local NUNIQUE counts disjoint value
    sets, and the integer COUNTSUM combine over a second (tiny,
    group-sized) exchange sums them — bit-identical to the unsalted
    plan, at the price of that extra small exchange.
    """
    from ..table import Table, _groupby_output_names, _local_groupby, _shard_wise

    names_out = _groupby_output_names(t, by_idx, aggs)
    ctx = t.ctx

    if pre_partitioned and any(op == AggOp.NUNIQUE for _, op in aggs):
        raise CylonError(Code.Invalid,
                         "pre_partitioned group-by cannot carry NUNIQUE "
                         "(no partial/combine decomposition)")
    salt = int(salt)
    if salt > 1 and (pre_partitioned
                     or any(op != AggOp.NUNIQUE for _, op in aggs)
                     or len({ci for ci, _ in aggs}) != 1):
        raise CylonError(Code.Invalid,
                         "salted group-by requires the all-NUNIQUE "
                         "single-distinct-column shape")
    if any(op == AggOp.NUNIQUE for _, op in aggs):
        # NUNIQUE does not decompose into partial+combine columns; instead
        # co-locate raw rows by key (shuffle) and run ONE local group-by —
        # exact, because groups are disjoint across shards after the
        # shuffle.  When every agg is NUNIQUE, traffic shrinks first via a
        # local distinct pass over the involved columns (duplicate
        # (key,value) rows cannot change a distinct count).
        from ..ops import unique as unique_mod

        involved = tuple(dict.fromkeys(
            tuple(by_idx) + tuple(ci for ci, _ in aggs)))
        work = t.project(involved)  # shuffle only the columns the aggs touch
        remap = {ci: i for i, ci in enumerate(involved)}
        by_p = tuple(remap[i] for i in by_idx)
        aggs_p = tuple((remap[ci], op) for ci, op in aggs)
        if all(op == AggOp.NUNIQUE for _, op in aggs):
            nn = work.names

            def dedup_fn(tt):
                cols, m = unique_mod.unique(
                    tt.columns, tt.row_counts[0],
                    tuple(range(len(involved))), "first")
                return Table(cols, jnp.reshape(m, (1,)), nn, ctx)

            work = _shard_wise(ctx, dedup_fn, work,
                               key=("nunique_dedup", involved))
        if salt > 1:
            from ..ops import hashing as hashing_mod

            vpos = aggs_p[0][0]
            nkeys = len(by_p)
            sn = work.names + ("__salt__",)

            def salt_fn(tt):
                bucket = (hashing_mod.hash_columns([tt.columns[vpos]])
                          % jnp.uint32(salt)).astype(jnp.int32)
                live = jnp.arange(bucket.shape[0],
                                  dtype=jnp.int32) < tt.row_counts[0]
                cols = tuple(tt.columns) + (
                    Column(bucket, live, None, dtypes.int32),)
                return Table(cols, tt.row_counts, sn, ctx)

            salted = _shard_wise(ctx, salt_fn, work,
                                 key=("nunique_salt", vpos, salt))
            spread = shuffle(salted, by_p + (len(involved),))
            part = _local_groupby(spread, by_p, aggs_p, ddof,
                                  pipeline=False)
            combined = shuffle(part, tuple(range(nkeys)))
            out = _local_groupby(
                combined, tuple(range(nkeys)),
                tuple((nkeys + i, AggOp.COUNTSUM)
                      for i in range(len(aggs_p))), ddof, pipeline=False)
            obs_spans.instant("shuffle.salted", buckets=salt, keys=nkeys)
            return out.rename(names_out)
        shuffled = shuffle(work, by_p)
        out = _local_groupby(shuffled, by_p, aggs_p, ddof, pipeline=False)
        return out.rename(names_out)

    # 1. expand requested aggs into partial ops, dedup
    partial_list, partial_index = groupby_partial_plan(aggs)

    nkeys = len(by_idx)

    # 2. local partial aggregate (per shard)
    local_partial = (groupby_mod.pipeline_groupby if pipeline
                     else groupby_mod.hash_groupby)

    def partial_fn(tt):
        cols, m = local_partial(
            tt.columns, tt.row_counts[0], tuple(by_idx), tuple(partial_list), ddof)
        pnames = tuple(f"k{i}" for i in range(nkeys)) + tuple(
            f"p{i}" for i in range(len(partial_list)))
        return Table(cols, jnp.reshape(m, (1,)), pnames, ctx)

    partial = _shard_map(ctx, partial_fn,
                         ("gb_partial", tuple(by_idx), tuple(partial_list),
                          ddof, pipeline),
                         _shapes_key(t))(t)

    # 3. shuffle partials on the key columns — unless the caller proved
    # the input pre-partitioned (every group's rows, hence its single
    # partial, already live on one shard)
    shuffled = partial if pre_partitioned else shuffle(
        partial, tuple(range(nkeys)))

    # 4. final combine: SUM of sums/counts/sumsqs, MIN of mins, MAX of maxes
    final_aggs = tuple((nkeys + i, groupby_mod.combine_op(pop))
                       for i, (_, pop) in enumerate(partial_list))
    key_range = tuple(range(nkeys))

    def final_fn(tt):
        cols, count = tt.columns, tt.row_counts[0]
        if pipeline:  # received partials arrive unsorted: sort, then scan
            cols, count = sort_mod.sort_rows(
                cols, count, key_range, tuple([True] * nkeys), True)
            cols, m = groupby_mod.pipeline_groupby(
                cols, count, key_range, final_aggs, ddof)
        else:
            cols, m = groupby_mod.hash_groupby(
                cols, count, key_range, final_aggs, ddof)
        return cols, jnp.reshape(m, (1,))

    fcols, fcounts = _shard_map(
        ctx, final_fn, ("gb_final", key_range, final_aggs, ddof, pipeline),
        _shapes_key(shuffled))(shuffled)

    # 5. finalize derived outputs (MEAN/VAR/STDDEV) from combined partials
    out_cols = finalize_groupby_columns(fcols, nkeys, aggs, partial_index,
                                        ddof)
    out = Table(tuple(out_cols), fcounts, names_out, ctx)
    if not pre_partitioned:
        # placed by the partial shuffle's hash of ALL group keys; a
        # pre-partitioned run is placed by the caller's key SUBSET
        # instead, which only the planner knows — it stamps its own
        out._partitioning = ("hash", (tuple(names_out[:nkeys]),),
                             t.num_shards)
    return out


# ---------------------------------------------------------------------------
# distributed scalar aggregates (reference: compute/aggregates.cpp DoAllReduce)
# ---------------------------------------------------------------------------

def distributed_scalar_agg(t, col_idx: int, op: agg_mod.ReduceOp):
    """Local masked reduce + ONE collective combine, all in a single program
    (the shape of the reference's arrow::compute + mpi::AllReduce,
    compute/aggregates.cpp:30-156).  Empty shards contribute the op's
    neutral element (scalar_agg's sentinels), so no host-side masking."""
    from . import collectives

    ctx = t.ctx

    def fn(tt):
        v, n = agg_mod.scalar_agg(tt.columns[col_idx], tt.row_counts[0], op)
        if op in (agg_mod.ReduceOp.SUM, agg_mod.ReduceOp.COUNT):
            r = collectives.allreduce_sum(v)
        elif op == agg_mod.ReduceOp.MIN:
            r = collectives.allreduce_min(v)
        elif op == agg_mod.ReduceOp.MAX:
            r = collectives.allreduce_max(v)
        elif op == agg_mod.ReduceOp.PROD:  # XLA has no pprod collective
            r = jnp.prod(collectives.allgather(jnp.reshape(v, (1,))))
        else:
            raise ValueError(op)
        return jnp.reshape(r, (1,))

    vals = _shard_map(ctx, fn, ("scalar", col_idx, op), _shapes_key(t))(t)
    return vals[0]
