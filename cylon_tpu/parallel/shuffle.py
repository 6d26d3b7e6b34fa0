"""The all-to-all table shuffle — the framework's central primitive.

TPU-native replacement for the reference's entire shuffle stack:
``PartitionByHashing -> Split -> ArrowAllToAll`` (reference:
cpp/src/cylon/partition/partition.cpp:24-114, arrow/arrow_all_to_all.cpp:
24-236, net/ops/all_to_all.cpp:26-178, table.cpp:67-152
all_to_all_arrow_tables).  Where the reference streams each buffer with 6-int
headers through per-peer MPI state machines and busy-waits on progress
loops, here the whole exchange is ONE jit program per shard:

1. group rows by target shard with a stable counting scan over the
   world-sized target alphabet (the Split kernel's per-row appends,
   arrow_kernels.hpp:60-96, become one cumsum per target + a gather),
2. per-target counts via segment-sum; an ``all_gather`` of the count row
   replaces the length-header handshake (the receiver "pre-allocation" is
   the static bucket size),
3. rows are laid into fixed-size per-target buckets and exchanged over
   ICI/DCN — by default on TPU as ONE tiled ``lax.all_to_all`` over a
   single bit-packed u32 plane carrying every column's data/validity/
   lengths (``parallel/plane.py``; ``CYLON_TPU_SHUFFLE_PACK`` gates it),
   otherwise one collective per buffer,
4. received buckets are compacted to the front with one searchsorted-gather
   (on the plane when packed — one gather total instead of one per buffer),
   yielding a front-packed shard + new row count.

Raggedness is the hard part on TPU (static shapes): bucket size is a static
parameter.  ``plan_shuffle`` computes the exact count matrix on-device and
lets the host pick the padded bucket size (rounded to a power of two so jit
caches stay warm); ``shuffle_shard`` is the fully static kernel usable
inside larger fused programs.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..column import Column
from ..obs import spans as obs_spans
from ..ops import compact as compact_mod
from ..status import Code, CylonError
from . import collectives
from . import plane as plane_mod


# Alphabet width above which the per-target unroll (_perm_by_target) and
# the dense alphabet compare (target_counts) both switch to sort-based
# derivations; the two predicates must stay identical so count derivation
# and permutation grouping never desynchronize.
_WIDE_MESH_CUTOFF = 32


def buffer_count(cols: Sequence[Column]) -> int:
    """Exchanged buffers per row set under the per-buffer realization —
    data + validity (+ lengths for strings) per column.  The single
    source behind the per-buffer collective-launch count: the span
    ``launches`` attrs here and the ``shuffle.collective_launches``
    metric (parallel/ops.py) must never disagree with the budget
    goldens on what counts as a launch."""
    return sum(2 + (1 if c.lengths is not None else 0) for c in cols)


def target_counts(targets: jax.Array, world: int) -> jax.Array:
    """int32[world]: rows this shard sends to each target (padding rows carry
    target == world and fall off the end).

    sort permute mode, narrow mesh: a fused compare-and-reduce over the
    tiny target alphabet — one bandwidth-bound pass, no scatter-add
    (XLA:TPU serializes scatters; see compact.permute_mode).  Wide mesh
    (same ``world + 1 > 32`` predicate as _perm_by_target's unroll
    cutoff): the O(cap*world) broadcast intermediate would dwarf the rows
    themselves (world=256 at a 64M-row chunk is a 2^34 compare unless XLA
    fuses it — round-4 advice finding 2), so counts come from one sort +
    count_leq_dense instead: counts[t] = #{targets <= t} - #{targets <= t-1}."""
    if compact_mod.permute_mode() == "sort":
        if world + 1 <= _WIDE_MESH_CUTOFF:
            alphabet = jnp.arange(world, dtype=targets.dtype)
            return jnp.sum(targets[:, None] == alphabet[None, :], axis=0,
                           dtype=jnp.int32)
        # count_leq_dense clips negatives to 0, which would misroute them
        # into target 0's count — remap to padding first (it takes any
        # input order: the packed merge sorts internally)
        t = _remap_oob_targets(targets, world)
        leq = compact_mod.count_leq_dense(t, world)
        return jnp.diff(leq, prepend=0).astype(jnp.int32)
    ones = jnp.ones_like(targets, dtype=jnp.int32)
    return jax.ops.segment_sum(ones, targets, world + 1)[:world]


def _remap_oob_targets(targets: jax.Array, world: int) -> jax.Array:
    """Out-of-range targets — negative included — become PADDING (== world),
    so a producer bug drops rows into padding (visible as count loss
    downstream) instead of silently misrouting them to rank 0, a
    legitimate destination.  Single-sourced: target_counts and
    _perm_by_target must never disagree on this policy."""
    return jnp.where((targets < 0) | (targets > world), world, targets)


def _perm_by_target(targets: jax.Array, world: int, *payload: jax.Array):
    """``(perm, *carried)``: the stable permutation grouping rows by target,
    padding (== world) last, and each 1-D ``payload`` array as
    ``jnp.take(x, perm)`` would return it.

    The target alphabet is tiny (world + 1 values), so a counting scan —
    one cumsum per target value, unrolled at trace time — replaces the
    stable sort the Split kernel would otherwise pay
    (reference: arrow_kernels.hpp:60-96 appends per-target builders row by
    row; here each target's rows get destinations base_t + rank-in-target).
    Where permutations sort, and for wide meshes where the unroll would
    bloat the program, ``compact.sort_by_target`` sorts instead and the
    payload rides that sort.

    Precondition: targets in [0, world] (world == padding).  Producers
    (hash_targets/range_targets) guarantee it; out-of-range values — negative
    included — are remapped to the PADDING bucket, so a producer bug drops
    rows into padding (visible as count loss downstream) instead of silently
    misrouting them to rank 0, a legitimate destination."""
    cap = targets.shape[0]
    targets = _remap_oob_targets(targets, world)
    if world + 1 > _WIDE_MESH_CUTOFF or compact_mod.permute_mode() == "sort":
        return compact_mod.sort_by_target(targets, world, *payload)
    dest = jnp.zeros((cap,), jnp.int32)
    base = jnp.zeros((), jnp.int32)
    for t in range(world + 1):
        m = targets == t
        c = jnp.cumsum(m.astype(jnp.int32))
        dest = jnp.where(m, base + c - 1, dest)
        base = base + c[-1]
    perm = jnp.zeros((cap,), jnp.int32).at[dest].set(
        jnp.arange(cap, dtype=jnp.int32))
    return (perm, *(jnp.take(x, perm) for x in payload))


def riding_words(words: int) -> int:
    """How many of a packed plane's ``words`` the ragged exchange carries
    through its own target sort: none where permutations scatter, else up
    to the payload a sort carries (``compact.MAX_PAYLOAD_LANES``); the rest
    go through the permutation.  Shared with the exchange's counters
    (``parallel/ops.py::_record_exchange``)."""
    if compact_mod.permute_mode() != "sort":
        return 0
    return min(words, compact_mod.MAX_PAYLOAD_LANES)


def _plane_by_target(targets: jax.Array, world: int, words):
    """``(perm, plane)``: ``_perm_by_target``'s permutation, and the
    ``uint32[cap, len(words)]`` plane whose columns are ``words``, its
    rows grouped by target.  The words that ride (``riding_words``) are
    payload of ``_perm_by_target``; the rest are taken through its
    permutation."""
    ride = riding_words(len(words))
    perm, *carried = _perm_by_target(targets, world, *words[:ride])
    parts = [jnp.stack(carried, axis=1)] if carried else []
    if ride < len(words):
        parts.append(jnp.take(jnp.stack(words[ride:], axis=1), perm, axis=0))
    return perm, jnp.concatenate(parts, axis=1)


def _append_held(out_cols: Tuple[Column, ...], total, cols, perm, counts,
                 count, block_rows: int, world: int):
    """``(columns, total)``: the exchange's result with every shard's
    held-back rows after its received ones, on every shard.

    A live row whose target is padding (``world``) is held back: it is
    not sent.  ``perm`` groups rows by target and keeps their order inside
    a target, and padding rows lie past every live row, so the rows held
    back are ``perm[sent:count]``.  They go into a block of ``block_rows``
    rows (the caller holds back no more), whose plane is gathered from
    every shard in ONE collective, compacted and written at ``total``:
    the receive capacity has room for ``world * block_rows`` rows past
    the fullest shard's received ones (``plan_shuffle``'s ``extra``)."""
    from ..ops import compact as compact_mod

    cap = perm.shape[0]
    sent = jnp.sum(counts, dtype=jnp.int32)
    held = count.astype(jnp.int32) - sent
    j = jnp.arange(block_rows, dtype=jnp.int32)
    idx = jnp.take(perm, jnp.clip(sent + j, 0, cap - 1))
    block = tuple(c.take(idx, valid_mask=j < held) for c in cols)
    plane = plane_mod.pack_plane(block)
    meta = jnp.zeros((1, plane.shape[1]), plane.dtype).at[0, 0].set(
        held.astype(plane.dtype))
    got = collectives.allgather(jnp.concatenate([plane, meta]), axis=0)
    n = got[:, block_rows, 0].astype(jnp.int32)
    rows = got[:, :block_rows, :].reshape(world * block_rows, -1)
    live = (j[None, :] < n[:, None]).reshape(world * block_rows)
    order, m = compact_mod.compact_indices(live)
    gathered = plane_mod.unpack_plane(
        jnp.take(rows, order, axis=0),
        cols, valid_mask=jnp.arange(world * block_rows, dtype=jnp.int32) < m)

    def put(buf, part):
        return jax.lax.dynamic_update_slice_in_dim(buf, part, total, 0)

    out = tuple(
        Column(put(o.data, g.data), put(o.validity, g.validity),
               None if o.lengths is None else put(o.lengths, g.lengths),
               o.dtype)
        for o, g in zip(out_cols, gathered))
    return out, total + m.astype(total.dtype)


def shuffle_shard(cols: Tuple[Column, ...], count, targets: jax.Array,
                  world: int, bucket: int, out_capacity: int, spec=None,
                  held_rows: int = 0):
    """Shard-local body of the shuffle (run under shard_map).

    bucket: static per-(src,dst) bucket row capacity; rows beyond it would be
    dropped, so callers size it from the count matrix (plan_shuffle) or use a
    safe bound (shard capacity).
    Returns (columns, new_count) with per-shard capacity ``out_capacity``.

    Exchange realization (``plane.pack_enabled()``, read at trace time):
    packed — every column's data/validity/lengths bit-packed into one u32
    plane, ONE ``all_to_all`` total, bucket-lay/compaction gathers run once
    on the plane; per-buffer — one collective and one gather pair per
    buffer.  Both produce bit-identical shards (tests/test_shuffle_pack.py).

    ``spec`` (packed realization only): the observed compression spec the
    caller derived from the pre-pass stats — narrow/dictionary/truncated
    plane fields, bit-exact round trip, at most one extra dictionary
    all_gather (plane.PlaneCodec).  Data-dependent static layout: callers
    key their jit-plan caches on it (cylint CY109).

    ``held_rows`` > 0: the live rows whose target is padding go to every
    shard instead, after its received rows (``_append_held``, at most
    ``held_rows`` of them a shard)."""
    cap = cols[0].data.shape[0]

    counts = target_counts(targets, world)
    # group rows by target: rows for shard t become contiguous, padding last
    perm_t, = _perm_by_target(targets, world)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(counts, dtype=jnp.int32)[:-1]])

    # lay rows into W fixed-size buckets: send slot (t, k) <- sorted row start[t]+k
    o = jnp.arange(world * bucket, dtype=jnp.int32)
    t = o // bucket
    k = o % bucket
    src_sorted = jnp.take(start, t) + k
    send_valid = k < jnp.take(counts, t)
    src = jnp.take(perm_t, jnp.clip(src_sorted, 0, cap - 1))

    # count matrix row exchange replaces the length-header protocol.
    # The spans here (and below) fire at TRACE time — this body runs on
    # the host under shard_map tracing — so each plan build nests
    # counts-gather/pack/collective/unpack children under the enclosing
    # shuffle.exchange span; no tracer is ever read (cylint CY101).
    with obs_spans.span("shuffle.counts_gather", world=world):
        cm = collectives.allgather(counts, axis=0).reshape(world, world)
    me = collectives.my_rank()
    incoming = cm[:, me]
    csum = jnp.cumsum(incoming, dtype=jnp.int32)
    total = csum[-1]

    # front-pack the received buckets: slot o2 <- bucket s, offset within
    o2 = jnp.arange(out_capacity, dtype=jnp.int32)
    s = jnp.clip(jnp.searchsorted(csum, o2, side="right").astype(jnp.int32),
                 0, world - 1)
    within = o2 - (jnp.take(csum, s) - jnp.take(incoming, s))
    src2 = jnp.clip(s * bucket + within, 0, world * bucket - 1)
    valid2 = o2 < total

    if plane_mod.pack_enabled():
        # ONE collective for the whole table: pack at shard capacity,
        # bucket-lay the plane (single gather), exchange, compact (single
        # gather), decode with the tail mask.  The codec applies the
        # compression spec (identity when spec is None); dictionary
        # columns cost one extra small all_gather at codec build.
        codec = plane_mod.PlaneCodec(cols, spec)
        with obs_spans.span("shuffle.pack", columns=len(cols)) as sp:
            packed = codec.pack(cols)
            sp.set(words=int(packed.shape[1]), compressed=spec is not None)
            send_plane = jnp.where(send_valid[:, None],
                                   jnp.take(packed, src, axis=0), 0)
        with obs_spans.span("shuffle.collective", family="all_to_all",
                            packed=True, launches=1):
            recv_plane = collectives.all_to_all(send_plane)
        with obs_spans.span("shuffle.unpack", columns=len(cols)):
            out_plane = jnp.take(recv_plane, src2, axis=0)
            out = codec.unpack(out_plane, cols, valid_mask=valid2)
        if held_rows:
            return _append_held(out, total, cols, perm_t, counts, count,
                                held_rows, world)
        return out, total

    # per-buffer exchange: one tiled all_to_all per buffer
    # (data/validity/lengths) — the whole ArrowAllToAll machinery, but
    # O(buffers x columns) collective launches
    with obs_spans.span("shuffle.pack", columns=len(cols), packed=False):
        send_cols = tuple(c.take(src, valid_mask=send_valid) for c in cols)
    with obs_spans.span("shuffle.collective", family="all_to_all",
                        packed=False, launches=buffer_count(cols)):
        recv_cols = tuple(
            Column(collectives.all_to_all(c.data),
                   collectives.all_to_all(c.validity),
                   None if c.lengths is None
                   else collectives.all_to_all(c.lengths),
                   c.dtype)
            for c in send_cols)
    with obs_spans.span("shuffle.unpack", columns=len(cols)):
        out_cols = tuple(c.take(src2, valid_mask=valid2) for c in recv_cols)
    if held_rows:
        return _append_held(out_cols, total, cols, perm_t, counts, count,
                            held_rows, world)
    return out_cols, total


def plan_shuffle(counts: jax.Array, extra: int = 0) -> Tuple[int, int]:
    """Host-side sizing from the [world, world] count matrix: (bucket,
    out_capacity), both rounded to powers of two to bound recompilation.
    ``extra``: rows every shard receives besides the matrix's (the held
    rows of ``_append_held``), a constant, so the capacity depends on the
    data through the matrix alone."""
    import numpy as np

    from ..utils import pow2ceil

    cm = np.asarray(counts)
    bucket = int(cm.max()) if cm.size else 0
    incoming = cm.sum(axis=0).max() if cm.size else 0
    return pow2ceil(bucket), pow2ceil(int(incoming) + extra)


def ragged_plan(cm, me):
    """Rank ``me``'s RaggedAllToAll sizing from the [world, world] count
    matrix (cm[src, dst] = rows src sends to dst): (recv_sizes,
    output_offsets, total).  ``output_offsets[t]`` is where my slice lands
    on receiver t — after every lower-ranked source's slice — so received
    rows arrive front-packed with no compaction pass.  Pure math shared by
    the device kernel and the host-side emulation tests."""
    world = cm.shape[0]
    recv_sizes = cm[:, me]
    src_rank = jnp.arange(world, dtype=jnp.int32)
    output_offsets = jnp.sum(
        jnp.where((src_rank < me)[:, None], cm, 0), axis=0).astype(jnp.int32)
    total = jnp.sum(recv_sizes, dtype=jnp.int32)
    return recv_sizes, output_offsets, total


#: XLA:TPU lays every row of a RaggedAllToAll operand out as one 128-lane
#: u32 vector, whatever the plane's width, and a send operand of 2^31 bytes
#: in that layout halts the core with a DMA bounds check (v5e, libtpu
#: 0.0.34: 4,190,000 rows per shard pass, 2^22 halt — PERF.md, PR 22).  A
#: shard under the limit goes through one collective; a larger one goes in
#: rounds whose send operand and receive buffer are both half the limit.
RAGGED_ROW_BYTES = 128 * 4
_RAGGED_OPERAND_LIMIT = 1 << 31


def ragged_round_quota(shard_capacity: int, world: int):
    """Rows a source sends to ONE destination in a round of the ragged
    exchange, or None where a shard of ``shard_capacity`` rows is under the
    collective's operand limit and goes whole.  ``world`` quotas fill a
    round's send operand (a source's rows for every destination) and its
    receive buffer (every source's rows for one destination), so neither
    can pass half the limit whatever the targets are."""
    if shard_capacity * RAGGED_ROW_BYTES < _RAGGED_OPERAND_LIMIT:
        return None
    return _RAGGED_OPERAND_LIMIT // RAGGED_ROW_BYTES // 2 // world


def plan_rounds(cm, shard_capacity: int) -> Tuple[int, int]:
    """Host-side sizing from the count matrix the plan already fetched:
    (rounds, rows in each shard's send operands over the whole exchange).
    Round r moves rows [r * quota, (r + 1) * quota) of every (src, dst)
    segment, so the fullest segment sets the count."""
    import numpy as np

    world = cm.shape[0]
    quota = ragged_round_quota(shard_capacity, world)
    if quota is None:
        return 1, shard_capacity
    rounds = max(1, -(-int(np.max(cm)) // quota))
    return rounds, rounds * world * quota


def ragged_round_plan(cm, me, r, quota: int):
    """Rank ``me``'s sizing of round ``r``: (send_sizes, recv_sizes,
    output_offsets, landing).  The round moves rows [r * quota,
    (r + 1) * quota) of every (src, dst) segment of the count matrix;
    ``output_offsets[t]`` is where my slice lands in receiver t's round
    buffer (after every lower-ranked source's), ``landing`` where that
    buffer's rows go in my dense output: after all I received in earlier
    rounds.  Closed form in ``r``, so a loop over rounds needs no carried
    offsets; shared by the device kernel and the host-side emulation."""
    world = cm.shape[0]
    moved = jnp.clip(cm - r * quota, 0, quota).astype(jnp.int32)
    src_rank = jnp.arange(world, dtype=jnp.int32)
    output_offsets = jnp.sum(
        jnp.where((src_rank < me)[:, None], moved, 0), axis=0).astype(jnp.int32)
    landing = jnp.sum(jnp.minimum(cm[:, me], r * quota), dtype=jnp.int32)
    return moved[me], moved[:, me], output_offsets, landing


def _ragged_exchange(sorted_buf: jax.Array, cm: jax.Array, me,
                     out_capacity: int, rounds):
    """One 2-D buffer whose rows are grouped by target, through
    ``ragged_all_to_all`` into a dense ``[out_capacity, ...]`` result.

    Under the operand limit: one collective over the whole buffer, rows
    landing in source-rank order.  Over it: ``rounds`` collectives (a
    count the caller sized with ``plan_rounds`` from the same count
    matrix), each sending ``quota`` rows at most of every destination's
    segment from an operand of ``world * quota`` rows and receiving into a
    buffer of the same size, which lands in the result after the earlier
    rounds' rows: round-major, source-rank order inside a round."""
    cap, width = sorted_buf.shape
    world = cm.shape[0]
    counts = cm[me]
    starts = (jnp.cumsum(counts, dtype=jnp.int32) - counts).astype(jnp.int32)
    quota = ragged_round_quota(cap, world)
    if quota is None:
        recv_sizes, output_offsets, _ = ragged_plan(cm, me)
        out = jnp.zeros((out_capacity, width), sorted_buf.dtype)
        return collectives.ragged_all_to_all(
            sorted_buf, out, starts, counts, output_offsets, recv_sizes)
    if rounds is None:
        raise CylonError(
            Code.CapacityError,
            f"ragged exchange of {cap} rows per shard goes in rounds: the "
            f"caller sizes them with plan_rounds from the count matrix")
    slots = jnp.arange(world, dtype=jnp.int32) * quota

    def one_round(r, landed):
        send_sizes, recv_sizes, output_offsets, landing = ragged_round_plan(
            cm, me, r, quota)
        # a slice that would run off the buffer's end starts earlier
        # instead, and the rows wanted lie ``want - begin`` into it; a
        # destination with nothing left is given an offset inside the
        # operand all the same
        want = starts + r * quota
        begin = jnp.clip(want, 0, cap - quota)
        operand = jnp.concatenate([
            jax.lax.dynamic_slice_in_dim(sorted_buf, begin[t], quota)
            for t in range(world)])
        input_offsets = jnp.where(send_sizes > 0, slots + want - begin, 0)
        got = collectives.ragged_all_to_all(
            operand, jnp.zeros_like(operand), input_offsets, send_sizes,
            output_offsets, recv_sizes)
        # the whole buffer lands, zeros past its rows included: the next
        # round overwrites them, the last leaves the tail zero
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(lane, got[:, w], landing, 0)
            for w, lane in enumerate(landed))

    # the result is carried a lane at a time: a [rows, width] carry that
    # is updated by rows gets a row-major layout on a TPU, a whole
    # 128-lane tile to a row (9.7 GB for three words of 2^24 rows)
    landed = jax.lax.fori_loop(0, rounds, one_round, tuple(
        jnp.zeros((out_capacity + world * quota,), sorted_buf.dtype)
        for _ in range(width)))
    return jnp.stack([lane[:out_capacity] for lane in landed], axis=1)


def shuffle_shard_ragged(cols: Tuple[Column, ...], targets: jax.Array,
                         world: int, out_capacity: int, spec=None,
                         rounds=None, held_rows: int = 0, count=None):
    """Skew-proof shard-local shuffle body over ``lax.ragged_all_to_all``.

    Where ``shuffle_shard`` pads every (src,dst) pair to one static bucket
    (traffic ``world x bucket`` rows per buffer — up to ~world x inflation
    when one shard is hot), this variant sends *exactly* the rows that
    exist: rows are stable-sorted by target so each destination's slice is
    contiguous, the all-gathered count matrix yields send/recv sizes and
    the packed output offsets, and XLA's RaggedAllToAll moves the slices.
    Received rows land front-packed, so no compaction gather is needed.

    A shard over the collective's operand limit (``ragged_round_quota``)
    goes in ``rounds`` rounds, a static count the caller takes from
    ``plan_rounds`` over the count matrix it planned ``out_capacity``
    from; a smaller shard goes whole and ``rounds`` is not read.  Rows of
    a received shard are in source-rank order when it went whole and in
    round-major order otherwise: no caller may rely on either.

    ``targets`` is taken as an argument (not recomputed) so the caller can
    reuse the targets pass that sized ``out_capacity`` — the reference
    similarly partitions once and streams only what exists
    (cpp/src/cylon/arrow/arrow_all_to_all.cpp:24-236).

    Exchange realization (``plane.pack_enabled()``, read at trace time):
    packed — the whole table travels as one bit-packed u32 plane through
    ONE ``ragged_all_to_all``, its words grouped by target inside the
    target sort where permutations sort (``_plane_by_target``);
    per-buffer — one collective and one sort-gather per buffer.
    Bit-identical outputs either way.

    ``held_rows`` > 0: the live rows of the shard's ``count`` whose target
    is padding go to every shard instead, after its received rows
    (``_append_held``, at most ``held_rows`` of them a shard).
    """
    counts = target_counts(targets, world)

    # on-device count-matrix exchange (the 6-int header protocol's job);
    # trace-time child spans, like shuffle_shard's (cylint CY101-clean)
    with obs_spans.span("shuffle.counts_gather", world=world):
        cm = collectives.allgather(counts, axis=0).reshape(world, world)
    me = collectives.my_rank()
    total = jnp.sum(cm[:, me], dtype=jnp.int32)

    if plane_mod.pack_enabled():
        codec = plane_mod.PlaneCodec(cols, spec)
        with obs_spans.span("shuffle.pack", columns=len(cols)) as sp:
            words = codec.pack_words(cols)
            sp.set(words=len(words), compressed=spec is not None)
            perm_t, sorted_plane = _plane_by_target(targets, world, words)
        with obs_spans.span("shuffle.collective",
                            family="ragged_all_to_all", packed=True,
                            launches=1, rounds=rounds or 1):
            got = _ragged_exchange(sorted_plane, cm, me, out_capacity,
                                   rounds)
        # NO validity mask on decode: the per-buffer path below moves raw
        # buffers (a null row's bytes pass through untouched), and the
        # plane must stay bit-identical to it; rows past ``total`` decode
        # from the zeros of ``out`` — validity False, zero data — exactly
        # like the unwritten tail of the per-buffer outputs.  Under a
        # compression spec zero fields no longer decode to zero VALUES
        # (offset / dictionary entry 0), so the tail is masked explicitly
        # — in-range null rows' raw payloads stay untouched.
        with obs_spans.span("shuffle.unpack", columns=len(cols)):
            tail = None
            if spec is not None:
                tail = jnp.arange(out_capacity, dtype=jnp.int32) < total
            out_cols = codec.unpack(got, cols, tail_mask=tail)
        if held_rows:
            return _append_held(out_cols, total, cols, perm_t, counts, count,
                                held_rows, world)
        return out_cols, total

    perm_t, = _perm_by_target(targets, world)

    def exchange(buf):
        squeeze = buf.ndim == 1
        if squeeze:  # RaggedAllToAll wants a payload axis
            buf = buf[:, None]
        orig = buf.dtype
        if orig == jnp.bool_:
            buf = buf.astype(jnp.uint8)
        got = _ragged_exchange(jnp.take(buf, perm_t, axis=0), cm, me,
                               out_capacity, rounds)
        if orig == jnp.bool_:
            got = got.astype(jnp.bool_)
        return got[:, 0] if squeeze else got

    with obs_spans.span("shuffle.collective", family="ragged_all_to_all",
                        packed=False, launches=buffer_count(cols),
                        rounds=rounds or 1):
        out_cols = tuple(
            Column(exchange(c.data), exchange(c.validity),
                   None if c.lengths is None else exchange(c.lengths),
                   c.dtype)
            for c in cols)
    if held_rows:
        return _append_held(out_cols, total, cols, perm_t, counts, count,
                            held_rows, world)
    return out_cols, total
