#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that cylon_tpu still starts on the chip.

Drives the engine's main path once on a TPU through the entry points a
user calls (``Table``, ``exec``, ``serve.QueryService``) and checks every
result against NumPy/pandas on the same data:

  P0 device   the platform must be exactly "tpu" (anything else: exit 2)
  P1 kernels  each kernel family at 2^20 rows, Pallas with interpret=False
  P2 main     join -> group-by -> sort at 2^24 rows/side, in core
  P3 wide     the same from int64/float64 pandas frames at 2^20 rows
  P4 ooc      exec.chunked_join_groupby on the P2 arrays, 4 passes
  P5 served   three QueryService requests, the third a journal cache hit

``--chips 4`` runs instead (and only) the sharded P2 on a four-device mesh
with the real exchange, at 2^23 rows per side in total.  One process, no child that imports JAX, no phase's
failure caught: the first phase that fails ends the run non-zero.  One JSON
line per phase goes to stdout; the last line is the contract line

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

This is bring-up evidence, not a benchmark: the seconds it prints are
first-call (compile + run) and second-call wall times of whole phases.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd

KERNEL_ROWS = 1 << 20
MAIN_ROWS = 1 << 24
WIDE_ROWS = 1 << 20
# XLA:TPU lays every row of a RaggedAllToAll out as one 128-lane vector
# (512 B in, 512 B out) and halts on a send operand of 2^31 such bytes
# (PERF.md, PR 22); a shard of 2^22 rows and more goes in rounds
# (parallel/shuffle.py, PR 27), this phase keeps to the one collective
SHUFFLE_ROWS = 1 << 21
# so four chips take 2^23 rows per side in total: 2^21 a shard, and the join's
# output shards (~2^21 rows, rounded up) stay under the limit as well
SHARDED_ROWS = 1 << 23
PASSES = 4
RTOL = 1e-4


def emit(phase: str, **fields) -> dict:
    rec = {"phase": phase, **fields}
    print(json.dumps(rec), flush=True)
    return rec


def make_data(rows: int, seed: int):
    """The bench recipe: uniform int32 keys in [0, rows), f32 values — an
    inner join of the two sides matches ~1:1."""
    rng = np.random.default_rng(seed)
    lk = rng.integers(0, rows, rows).astype(np.int32)
    lv = rng.random(rows).astype(np.float32)
    rk = rng.integers(0, rows, rows).astype(np.int32)
    rv = rng.random(rows).astype(np.float32)
    return lk, lv, rk, rv


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def twice(fn):
    """(first result, first-call seconds, second-call seconds): the first
    call pays tracing and compilation, the second runs from the caches."""
    t0 = time.perf_counter()
    out = fn()
    t1 = time.perf_counter()
    fn()
    return out, round(t1 - t0, 3), round(time.perf_counter() - t1, 3)


def realized_modes() -> dict:
    """What the trace-time 'auto' defaults resolve to in this process."""
    from cylon_tpu import precision
    from cylon_tpu.ops import compact, segments
    from cylon_tpu.parallel import plane

    return {"accumulation": precision.accumulation_mode(),
            "permute": compact.permute_mode(),
            "shuffle_pack": plane.pack_enabled(),
            "shuffle_compress": plane.compress_enabled(),
            "segsum": segments.effective_mode(),
            "pallas_native": precision.on_tpu()}


def assert_chip_modes(modes: dict) -> None:
    """The selection a TPU backend is supposed to make; the CPU tests run
    the other side of every one of these."""
    assert modes["accumulation"] == "narrow", modes
    assert modes["permute"] == "sort", modes
    assert modes["shuffle_pack"] and modes["shuffle_compress"], modes
    assert modes["segsum"] != "scatter", modes
    assert modes["pallas_native"], modes


# ---------------------------------------------------------------------------
# P0
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    import jax

    from cylon_tpu.utils.compile_cache import enable_persistent_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: platform is {devs[0].platform!r}, not 'tpu'",
              file=sys.stderr, flush=True)
        sys.exit(2)
    if chips == 4 and len(devs) != 4:
        print(f"chip_smoke: --chips 4 but JAX sees {len(devs)} device(s)",
              file=sys.stderr, flush=True)
        sys.exit(2)
    cache_dir = enable_persistent_compile_cache(min_compile_secs=1)
    emit("P0 device", platform=devs[0].platform, kind=devs[0].device_kind,
         devices=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


# ---------------------------------------------------------------------------
# P1: kernel families against NumPy/pandas
# ---------------------------------------------------------------------------

def first_and_second(calls: dict):
    """({name: result}, {name: [first_s, second_s]}).  The first calls run
    side by side — each compiles a program of its own, and the chip's
    compiler leaves most cores idle when they go one by one — so their
    seconds overlap; the second calls run one after another."""
    import jax

    def timed(fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        return out, round(time.perf_counter() - t0, 3)

    with ThreadPoolExecutor(len(calls)) as pool:
        futures = {name: pool.submit(timed, fn) for name, fn in calls.items()}
        firsts = {name: f.result() for name, f in futures.items()}
    seconds = {name: [first, timed(calls[name])[1]]
               for name, (_, first) in firsts.items()}
    return {name: out for name, (out, _) in firsts.items()}, seconds


def phase_kernels(rows: int, seed: int, interpret: bool) -> dict:
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from cylon_tpu import column as colmod
    from cylon_tpu import native, precision
    from cylon_tpu.config import JoinType
    from cylon_tpu.ops import groupby as gmod
    from cylon_tpu.ops import join as jmod
    from cylon_tpu.ops import pallas_kernels, pallas_scan, realization, segments
    from cylon_tpu.ops import sort as smod
    from cylon_tpu.ops import unique as umod
    from cylon_tpu.ops.groupby import AggOp

    rng = np.random.default_rng(seed)
    kh = rng.integers(0, max(1, rows // 4), rows).astype(np.int32)
    vh = rng.random(rows).astype(np.float32)
    rh = rng.random(rows) < 0.01
    rh[0] = True
    k, v = colmod.from_numpy(kh), colmod.from_numpy(vh)
    cnt = jnp.asarray(rows, jnp.int32)
    df = pd.DataFrame({"k": kh, "v": vh})
    merged = df.merge(df, on="k")
    out_cap = 1 << max(3, int(len(merged) - 1).bit_length())
    stats = (AggOp.SUM, AggOp.MEAN, AggOp.VAR)

    def join(algo):
        return lambda: jmod.join_gather((k, v), cnt, (k, v), cnt, (0,), (0,),
                                        JoinType.INNER, out_cap, algo)

    def groupby(ops):
        return lambda: gmod.hash_groupby((k, v), cnt, (0,),
                                         tuple((1, op) for op in ops), 0)

    outs, seconds = first_and_second({
        "sort_join": join("sort"),
        "hash_join": join("hash"),
        "groupby": groupby(stats + (AggOp.NUNIQUE,)),
        "sort": lambda: smod.sort_rows((k, v), cnt, (0,), (True,), True),
        "unique": lambda: umod.unique((k, v), cnt, (0,), "first"),
        "pallas_hash_partition": lambda: pallas_kernels.hash_partition(
            [k], 8, interpret=interpret),
        "pallas_segmented_scan": lambda: pallas_scan.segmented_scan(
            jnp.asarray(vh), jnp.asarray(rh), "sum", interpret=interpret),
    })

    # joins: the self-join's rows as a (k, l_v, r_v) multiset
    exp_join = merged.sort_values(["k", "v_x", "v_y"]).to_numpy(np.float64)
    for algo in ("sort", "hash"):
        cols, m = outs[f"{algo}_join"]
        m = int(m)
        assert m == len(merged), (algo, m, len(merged))
        got = pd.DataFrame({"k": np.asarray(cols[0].data)[:m],
                            "v_x": np.asarray(cols[1].data)[:m],
                            "v_y": np.asarray(cols[3].data)[:m]})
        got = got.sort_values(["k", "v_x", "v_y"]).to_numpy(np.float64)
        assert np.array_equal(got, exp_join), f"{algo} join rows differ"

    # group-by: keys in order, SUM/MEAN/VAR/NUNIQUE per key
    g64 = df.astype({"v": np.float64}).groupby("k")["v"]
    exp_g = g64.agg(["sum", "mean"])
    cols, ng = outs["groupby"]
    ng = int(ng)
    got_g = [np.asarray(c.data)[:ng] for c in cols]
    assert ng == len(exp_g), (ng, len(exp_g))
    assert np.array_equal(got_g[0], exp_g.index.values)
    np.testing.assert_allclose(got_g[1], exp_g["sum"].values, rtol=RTOL)
    np.testing.assert_allclose(got_g[2], exp_g["mean"].values, rtol=RTOL)
    # f32 sum of squares minus squared mean: an absolute error, not a
    # relative one
    np.testing.assert_allclose(got_g[3], g64.var(ddof=0).values, rtol=1e-2,
                               atol=1e-4)
    assert np.array_equal(got_g[4], df.groupby("k")["v"].nunique().values)

    # sort: keys in order, rows kept as a multiset
    cols, _ = outs["sort"]
    got = pd.DataFrame({"k": np.asarray(cols[0].data)[:rows],
                        "v": np.asarray(cols[1].data)[:rows]})
    assert np.array_equal(got.k.values, np.sort(kh))
    assert np.array_equal(got.sort_values(["k", "v"]).to_numpy(),
                          df.sort_values(["k", "v"]).to_numpy())

    # unique: keep-first rows in original order
    cols, nu = outs["unique"]
    nu = int(nu)
    exp_u = df.drop_duplicates("k", keep="first")
    assert nu == len(exp_u)
    assert np.array_equal(np.asarray(cols[0].data)[:nu], exp_u.k.values)
    assert np.array_equal(np.asarray(cols[1].data)[:nu], exp_u.v.values)

    # Pallas murmur3 hash-partition against the native host hasher
    assert native.available(), native.load_error()
    exp_h = native.row_hash([kh])
    h, t = outs["pallas_hash_partition"]
    assert np.array_equal(np.asarray(h)[:rows], exp_h)
    assert np.array_equal(np.asarray(t)[:rows], (exp_h % 8).astype(np.int32))

    # Pallas two-sweep segmented scan against a float64 NumPy one
    csum = np.cumsum(vh.astype(np.float64))
    starts = np.flatnonzero(rh)
    base = np.concatenate([[0.0], csum[starts[1:] - 1]])
    np.testing.assert_allclose(np.asarray(outs["pallas_segmented_scan"]),
                               csum - base[np.cumsum(rh) - 1], rtol=RTOL,
                               atol=1e-5)

    # the default segment reduction must agree with the scatter-add one:
    # this platform's row of the table with the other's ``segsum``.
    # Traced programs do not see the substitution, so they are dropped on
    # the way in and out (set_accumulation drops them too, when it changes
    # the mode)
    scatter_row = realization.current()._replace(segsum="scatter")
    precision.set_accumulation("narrow")
    jax.clear_caches()
    try:
        with mock.patch.object(realization, "current",
                               return_value=scatter_row):
            assert segments.effective_mode() == "scatter"
            arm, arm_seconds = first_and_second(
                {"groupby_scatter": groupby(stats)})
        seconds.update(arm_seconds)
        scat = [np.asarray(c.data)[:ng] for c in arm["groupby_scatter"][0]]
    finally:
        jax.clear_caches()
        precision.set_accumulation(None)
    for got, ref in zip(got_g[1:3], scat[1:3]):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    return {"rows": rows, "pallas_interpret": interpret,
            "segsum": segments.effective_mode(),
            "first_calls": "concurrent", "first_second_s": seconds}


# ---------------------------------------------------------------------------
# P2/P3: join -> group-by -> sort through Table, against pandas
# ---------------------------------------------------------------------------

def pandas_pipeline(left: pd.DataFrame, right: pd.DataFrame):
    """The oracle: (join row count, group-by frame, sorted left frame)."""
    merged = left.merge(right, on="k")
    gb = (merged.groupby("k")["a"].agg(["sum", "mean", "count"])
          .astype({"sum": np.float64, "mean": np.float64}).reset_index()
          .set_axis(["k", "sum_a", "mean_a", "count_a"], axis=1))
    return len(merged), gb, left.sort_values(["k", "a"], kind="stable")


def table_pipeline(left, right):
    """The path under test: distributed_join -> groupby -> distributed_sort
    -> to_pandas, through the public Table API."""
    joined = left.distributed_join(right, on="k", how="inner")
    gb = joined.groupby("l_k", {"a": ["sum", "mean", "count"]}).to_pandas()
    srt = left.distributed_sort("k").to_pandas()
    return joined.row_count, gb, srt


def compare_groupby(keys, sums, means, counts, exp_gb) -> None:
    """Keys and counts exact, f32-accumulated sums and means to RTOL."""
    assert len(keys) == len(exp_gb), (len(keys), len(exp_gb))
    order = np.argsort(keys, kind="stable")
    assert np.array_equal(np.asarray(keys)[order], exp_gb["k"].values)
    assert np.array_equal(np.asarray(counts)[order], exp_gb["count_a"].values)
    np.testing.assert_allclose(np.asarray(sums, np.float64)[order],
                               exp_gb["sum_a"].values, rtol=RTOL)
    np.testing.assert_allclose(np.asarray(means, np.float64)[order],
                               exp_gb["mean_a"].values, rtol=RTOL)


def compare_pipeline(got, exp) -> dict:
    (n_join, gb, srt), (exp_join, exp_gb, exp_srt) = got, exp
    assert n_join == exp_join, (n_join, exp_join)
    compare_groupby(gb["l_k"], gb["sum_a"], gb["mean_a"], gb["count_a"],
                    exp_gb)
    assert np.array_equal(srt["k"].values, exp_srt["k"].values)
    assert np.array_equal(srt.sort_values(["k", "a"]).to_numpy(),
                          exp_srt.to_numpy())
    return {"join_rows": int(n_join), "groups": len(gb),
            "sorted_rows": len(srt)}


def checked_pipeline(left, right, ldf, rdf) -> tuple:
    """Run the Table pipeline twice and hold the first result to the pandas
    oracle on the same frames: (record, the oracle's group-by frame)."""
    got, first, second = twice(lambda: table_pipeline(left, right))
    exp = pandas_pipeline(ldf, rdf)
    rec = compare_pipeline(got, exp)
    rec.update(rows_per_side=len(ldf), first_s=first, second_s=second)
    return rec, exp[1]


def exchange_family(ctx, expect_ragged: bool) -> str:
    from cylon_tpu.context import ctx_cache

    ragged = ctx_cache(ctx, "_ragged_probe").get("ragged")
    if expect_ragged:
        assert ragged is True, "the ragged exchange did not run"
    return "ragged" if ragged else "bucketed"


def forced_shuffle(ctx, data, expect_ragged: bool) -> dict:
    """A world-1 distributed_join takes the local fast path, so drive one
    hash exchange explicitly; rows must survive as a multiset."""
    from cylon_tpu import Table
    from cylon_tpu.parallel import ops as par_ops

    table = Table.from_numpy(["k", "a"], [c[:SHUFFLE_ROWS] for c in data[:2]],
                             ctx=ctx)
    out = par_ops._shuffled(table, (0,), "hash")
    family = exchange_family(ctx, expect_ragged)
    a = table.to_pandas().sort_values(["k", "a"]).to_numpy()
    b = out.to_pandas().sort_values(["k", "a"]).to_numpy()
    assert np.array_equal(a, b), "shuffle changed the rows"
    return {"family": family, "rows": int(out.row_count)}


def phase_main(ctx, data) -> tuple:
    from cylon_tpu import Table

    lk, lv, rk, rv = data
    return checked_pipeline(
        Table.from_numpy(["k", "a"], [lk, lv], ctx=ctx),
        Table.from_numpy(["k", "b"], [rk, rv], ctx=ctx),
        pd.DataFrame({"k": lk, "a": lv}), pd.DataFrame({"k": rk, "b": rv}))


def phase_wide(ctx, rows: int, seed: int) -> dict:
    """int64 keys and float64 values, as a CSV loaded with pandas gives:
    the accumulation mode decides the width of the sums, never the keys."""
    from cylon_tpu import Table

    lk, lv, rk, rv = make_data(rows, seed + 1)
    ldf = pd.DataFrame({"k": lk.astype(np.int64), "a": lv.astype(np.float64)})
    rdf = pd.DataFrame({"k": rk.astype(np.int64), "b": rv.astype(np.float64)})
    rec, _ = checked_pipeline(Table.from_pandas(ldf, ctx=ctx),
                              Table.from_pandas(rdf, ctx=ctx), ldf, rdf)
    return dict(rec, dtypes="int64/float64")


# ---------------------------------------------------------------------------
# P4/P5: out of core and served
# ---------------------------------------------------------------------------

def phase_out_of_core(data, exp_gb, scratch: str) -> dict:
    from cylon_tpu import config
    from cylon_tpu import exec as exec_mod
    from cylon_tpu.ops.groupby import AggOp

    aggs = ((1, AggOp.SUM), (1, AggOp.MEAN), (1, AggOp.COUNT))

    def run():
        # a fresh journal each time: both calls stream every pass through
        # host -> H2D -> kernel -> D2H (P5 shows the journal answering)
        with config.knob_env(CYLON_TPU_DURABLE_DIR=tempfile.mkdtemp(
                prefix="journal_", dir=scratch)):
            return exec_mod.chunked_join_groupby(*data, PASSES, aggs=aggs)

    (out, stats), first, second = twice(run)
    assert stats["passes"] == PASSES and stats.get("parts_run") == PASSES, stats
    compare_groupby(out["key"], out["agg0"], out["agg1"], out["agg2"], exp_gb)
    return {"rows_per_side": len(data[0]), "passes": stats["passes"],
            "mode": stats["mode"], "chunk_cap": stats["chunk_cap"],
            "groups": int(stats["groups"]), "first_s": first,
            "second_s": second}


def phase_served(ctx, data, exp_join_rows: int, exp_gb, scratch: str) -> dict:
    from cylon_tpu import config
    from cylon_tpu.serve import QueryService
    from cylon_tpu.serve import service as service_mod

    lk, lv, rk, rv = data
    left, right = {"k": lk, "a": lv}, {"k": rk, "b": rv}
    agg = {"a": ["sum", "mean", "count"]}
    tickets, seconds = [], []
    with config.knob_env(CYLON_TPU_DURABLE_DIR=tempfile.mkdtemp(
            prefix="serve_", dir=scratch)), QueryService(ctx) as svc:
        for op, kw in (("join", {}),
                       ("join_groupby", {"group_by": "l_k", "agg": agg}),
                       ("join", {})):
            t0 = time.perf_counter()
            t = svc.submit("smoke", op, left, right, on="k", passes=PASSES,
                           **kw)
            t.result(timeout=900)
            seconds.append(round(time.perf_counter() - t0, 3))
            tickets.append(t)
    assert all(t.state == service_mod.DONE for t in tickets), \
        [t.state for t in tickets]
    for t in (tickets[0], tickets[2]):
        res = t.result_value
        assert len(res["l_k"]) == exp_join_rows, len(res["l_k"])
        assert np.array_equal(res["l_k"], res["r_k"])
    a, c = tickets[0].result_value, tickets[2].result_value
    assert all(np.array_equal(a[n], c[n]) for n in a), "cache hit differs"
    g = tickets[1].result_value
    compare_groupby(g["l_k"], g["sum_a"], g["mean_a"], g["count_a"], exp_gb)
    assert not tickets[0].cache_hit and tickets[2].cache_hit, \
        [t.cache_hit for t in tickets]
    return {"requests": [t.op for t in tickets],
            "states": [t.state for t in tickets],
            "cache_hit": [t.cache_hit for t in tickets],
            "join_rows": exp_join_rows, "request_s": seconds}


# ---------------------------------------------------------------------------
# --chips 4: the sharded main path and what it is compared with
# ---------------------------------------------------------------------------

def assert_sharded(table, world: int) -> None:
    """Every buffer of every column sits on ``world`` distinct devices, a
    ``world``-th of the capacity each."""
    cap = table.capacity
    for name, col in zip(table.names, table.columns):
        for buf in (col.data, col.validity, col.lengths):
            if buf is None:
                continue
            shards = buf.addressable_shards
            devs = {s.device for s in shards}
            assert len(devs) == world, (name, devs)
            assert all(s.data.shape[0] * world == cap for s in shards), \
                (name, [s.data.shape for s in shards], cap)


def phase_sharded(ctx, data, expect_ragged: bool) -> dict:
    from cylon_tpu import Table
    from cylon_tpu.obs import metrics

    world = ctx.GetWorldSize()
    lk, lv, rk, rv = data
    left = Table.from_numpy(["k", "a"], [lk, lv], ctx=ctx)
    right = Table.from_numpy(["k", "b"], [rk, rv], ctx=ctx)
    assert left.num_shards == world
    assert_sharded(left, world)
    assert_sharded(right, world)
    sent0 = metrics.counter_value("shuffle.bytes_sent")
    rec, _ = checked_pipeline(left, right, pd.DataFrame({"k": lk, "a": lv}),
                              pd.DataFrame({"k": rk, "b": rv}))
    sent = int(metrics.counter_value("shuffle.bytes_sent") - sent0)
    family = exchange_family(ctx, expect_ragged)
    emit("exchange", family=family, bytes_sent=sent,
         exchanges=int(metrics.counter_value("shuffle.exchanges")))
    assert sent > 0, "nothing was exchanged"
    return dict(rec, world=world, family=family, bytes_sent=sent)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded main path on four chips")
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per side for every phase (default: 2^20 "
                         "kernels and wide columns, 2^24 main path, 2^23 "
                         "in total on four chips)")
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args(argv)

    from cylon_tpu import CylonContext, TPUConfig

    device = phase_device(args.chips)
    rows = args.rows
    modes = realized_modes()
    ctx = CylonContext.InitDistributed(TPUConfig(world_size=args.chips))
    if args.chips == 4:
        data = make_data(rows or SHARDED_ROWS, args.seed)
        assert_chip_modes(modes)
        emit("P2x4 sharded main path", modes=modes,
             **phase_sharded(ctx, data, expect_ragged=True),
             peak_bytes=peak_bytes())
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0

    data = make_data(rows or MAIN_ROWS, args.seed)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as scratch, \
            ThreadPoolExecutor(1) as side:
        # flight dumps and journals land under the system temp dir, never
        # in the checkout
        os.environ["CYLON_TPU_TRACE_DIR"] = os.path.join(scratch, "traces")
        emit("P1 kernels", **phase_kernels(rows or KERNEL_ROWS, args.seed,
                                           interpret=False),
             peak_bytes=peak_bytes())
        assert_chip_modes(modes)
        # P3's 64-bit programs take the chip's compiler three times as long
        # as P2's and share nothing with them: they compile on the side
        # while P2 and P4 run (after P1, which flips trace-time modes)
        wide = side.submit(phase_wide, ctx, rows or WIDE_ROWS, args.seed)
        rec, exp_gb = phase_main(ctx, data)
        emit("P2 main path", modes=modes, **rec,
             shuffle=forced_shuffle(ctx, data, expect_ragged=True),
             peak_bytes=peak_bytes())
        emit("P4 out of core", **phase_out_of_core(data, exp_gb, scratch),
             peak_bytes=peak_bytes())
        emit("P3 wide columns", **wide.result(), alongside="P2, P4",
             peak_bytes=peak_bytes())
        emit("P5 served", **phase_served(ctx, data, rec["join_rows"], exp_gb,
                                         scratch),
             peak_bytes=peak_bytes())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
