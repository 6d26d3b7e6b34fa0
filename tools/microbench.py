"""Primitive-op cost model for the current backend.

Times the building blocks every kernel composes — sorts (1/2/3 operand),
gathers (random / sorted indices), scatters (permute-set / add), scans
(cumsum / cummax) — at N elements, so design choices (the table in
cylon_tpu/ops/realization.py: sort-vs-scatter, scan and segment-reduction
realizations) rest on measured per-op costs instead of folklore.
Round-4 motivation: the first hardware window
showed lax.sort at 213 ms vs ~900 ms per permuting scatter at 64M
elements, inverting the CPU cost model.

Usage: python tools/microbench.py [n_elements]   (default 2^26)
Prints one line per op: name, ms (best of 3), GB/s of minimal traffic.
"""
import os
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cylon_tpu.utils.compile_cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

from cylon_tpu.obs import export as obs_export  # noqa: E402
from cylon_tpu.obs import spans as obs_spans  # noqa: E402

_POS_ARGS = [a for a in sys.argv[1:] if not a.startswith("--")]
N = int(_POS_ARGS[0]) if _POS_ARGS else (1 << 26)
REPS = 3


def _plan_ab(n_rows: int) -> bool:
    """ISSUE-9 A/B arm: join→groupby-on-same-key through the logical
    planner (CYLON_TPU_PLAN on) vs eager per-op lowering (off), on a
    mesh over every visible device.  Reports wall time (best of 3),
    collective launches, and shuffle.bytes_sent per arm — the planner's
    shuffle elision + column pruning should cut both collective counts
    (3 exchanges -> 2, or -> 1 for the shared-scan self-join) and bytes
    (the 12-column left table prunes to 2 before plane packing)."""
    from cylon_tpu import Table, config
    from cylon_tpu.context import CylonContext, TPUConfig
    from cylon_tpu.obs import metrics as obs_metrics

    ndev = len(jax.devices())
    if ndev < 2:
        # nonzero exit so the battery's `||` CPU-mesh fallback actually
        # fires — a silent rc=0 skip would leave the round with no A/B
        print("plan-ab: needs >= 2 devices for a mesh; skipping",
              flush=True)
        return False
    ctx = CylonContext.InitDistributed(TPUConfig(world_size=ndev))
    r = np.random.default_rng(17)
    # 12-column fact table: the planner prunes 10 dead columns before
    # the exchange; the eager arm ships all 12
    fact = {"k": r.integers(0, n_rows, n_rows).astype(np.int32),
            "v": r.random(n_rows).astype(np.float32)}
    for i in range(10):
        fact[f"pad{i}"] = r.random(n_rows).astype(np.float32)
    dim = {"k2": r.integers(0, n_rows, n_rows).astype(np.int32),
           "w": r.random(n_rows).astype(np.float32)}
    ft = Table.from_numpy(list(fact), list(fact.values()), ctx=ctx)
    dt_ = Table.from_numpy(list(dim), list(dim.values()), ctx=ctx)
    q = (ft.plan().join(dt_, left_on="k", right_on="k2")
         .groupby(["k"], {"v": ["sum"], "w": ["sum"]}))
    for label, mode in (("planner", "1"), ("eager", "0")):
        with config.knob_env(CYLON_TPU_PLAN=mode):
            q.execute()  # warm the stage caches
            best, deltas = None, None
            for _ in range(REPS):
                before = dict(obs_metrics.snapshot()["counters"])
                t0 = time.perf_counter()
                out = q.execute()
                out.row_count  # force completion
                dt_s = time.perf_counter() - t0
                after = dict(obs_metrics.snapshot()["counters"])
                if best is None or dt_s < best:
                    best = dt_s
                    deltas = {k: after.get(k, 0) - before.get(k, 0)
                              for k in ("shuffle.collective_launches",
                                        "shuffle.counts_gathers",
                                        "shuffle.bytes_sent",
                                        "plan.shuffles_elided")}
        print(f"plan-ab {label:8s} {best * 1e3:10.1f} ms  "
              f"launches={int(deltas['shuffle.collective_launches'])} "
              f"counts_gathers={int(deltas['shuffle.counts_gathers'])} "
              f"bytes_sent={int(deltas['shuffle.bytes_sent'])} "
              f"elided={int(deltas['plan.shuffles_elided'])}",
              flush=True)
    print("done", flush=True)
    return True


def _compress_ab(n_rows: int) -> bool:
    """ISSUE-10 A/B arm: one low-cardinality shuffle (narrow int keys +
    dictionary-friendly category strings, the TPC-H Q3 lineitem shape)
    with CYLON_TPU_SHUFFLE_COMPRESS off vs on, packed plane both arms.
    Reports bytes_sent, plane words/row, and wall time per arm — the
    compressed exchange must move the same rows in fewer bits while the
    shards stay bit-identical (tests pin that; this arm measures it)."""
    from cylon_tpu import Table, config
    from cylon_tpu.context import CylonContext, TPUConfig
    from cylon_tpu.obs import metrics as obs_metrics
    from cylon_tpu.parallel import plane as plane_mod

    ndev = len(jax.devices())
    if ndev < 2:
        # nonzero exit so the battery's `||` CPU-mesh fallback fires
        print("compress-ab: needs >= 2 devices for a mesh; skipping",
              flush=True)
        return False
    ctx = CylonContext.InitDistributed(TPUConfig(world_size=ndev))
    r = np.random.default_rng(23)
    flags = np.array(["A", "N", "R"], object)
    status = np.array(["F", "O"], object)
    arrs = {
        "l_orderkey": r.integers(0, n_rows, n_rows).astype(np.int32),
        "l_extendedprice": (r.random(n_rows, np.float32) * 90000 + 900),
        "l_discount": r.integers(0, 11, n_rows).astype(np.float32) / 100,
        "l_returnflag": flags[r.integers(0, 3, n_rows)],
        "l_linestatus": status[r.integers(0, 2, n_rows)],
        "l_shipdate": r.integers(0, 2556, n_rows).astype(np.int32),
    }
    t = Table.from_numpy(list(arrs), list(arrs.values()), ctx=ctx)
    for label, mode in (("plain", "0"), ("compressed", "1")):
        with config.knob_env(CYLON_TPU_SHUFFLE_PACK="1",
                             CYLON_TPU_SHUFFLE_COMPRESS=mode):
            words = plane_mod.plane_words(t.columns)
            if mode == "1":
                spec = plane_mod.estimate_spec(t.columns, ctx.GetWorldSize(),
                                               t.shard_capacity)
                words = plane_mod.plane_words(t.columns, spec)
            t.shuffle(["l_orderkey"])  # warm the plan caches
            best, deltas = None, None
            for _ in range(REPS):
                before = dict(obs_metrics.snapshot()["counters"])
                t0 = time.perf_counter()
                out = t.shuffle(["l_orderkey"])
                out.row_count  # force completion
                dt_s = time.perf_counter() - t0
                after = dict(obs_metrics.snapshot()["counters"])
                if best is None or dt_s < best:
                    best = dt_s
                    deltas = {k: after.get(k, 0) - before.get(k, 0)
                              for k in ("shuffle.bytes_sent",
                                        "shuffle.bytes_saved",
                                        "shuffle.collective_launches")}
        print(f"compress-ab {label:10s} {best * 1e3:10.1f} ms  "
              f"words/row={words} "
              f"bytes_sent={int(deltas['shuffle.bytes_sent'])} "
              f"bytes_saved={int(deltas['shuffle.bytes_saved'])} "
              f"launches={int(deltas['shuffle.collective_launches'])}",
              flush=True)
    print("done", flush=True)
    return True


def _adaptive_ab(n_rows: int) -> bool:
    """ISSUE-17 A/B arms: the adaptive planner's two strategies against
    the PR-9 plans they replace, on a mesh over every visible device.

    Arm 1 (broadcast-vs-shuffle): a fact table joins a tiny dimension;
    adaptive=on replicates the dimension with ONE all_gather while
    adaptive=off pays two full exchanges.  Arm 2 (salted-vs-plain): a
    zipfian-key NUNIQUE whose statistics catalog (seeded by one profiled
    run into a throwaway dir) shows shard skew; adaptive=on salts the
    repartition across value-hash buckets.  Both strategies are exact —
    tests pin bit-identity — so the arms measure launches, bytes and
    wall only."""
    import tempfile

    from cylon_tpu import Table, config
    from cylon_tpu.context import CylonContext, TPUConfig
    from cylon_tpu.obs import metrics as obs_metrics

    ndev = len(jax.devices())
    if ndev < 2:
        # nonzero exit so the battery's `||` CPU-mesh fallback fires
        print("adaptive-ab: needs >= 2 devices for a mesh; skipping",
              flush=True)
        return False
    ctx = CylonContext.InitDistributed(TPUConfig(world_size=ndev))
    r = np.random.default_rng(29)
    wanted = ("shuffle.collective_launches", "shuffle.bytes_sent",
              "plan.broadcast_joins", "plan.keys_salted")

    def run_arms(q, arms, env):
        for label, adaptive in arms:
            with config.knob_env(CYLON_TPU_PLAN="1",
                                 CYLON_TPU_PLAN_ADAPTIVE=adaptive, **env):
                q.execute()  # warm the stage caches
                best, deltas = None, None
                for _ in range(REPS):
                    before = dict(obs_metrics.snapshot()["counters"])
                    t0 = time.perf_counter()
                    out = q.execute()
                    out.row_count  # force completion
                    dt_s = time.perf_counter() - t0
                    after = dict(obs_metrics.snapshot()["counters"])
                    if best is None or dt_s < best:
                        best = dt_s
                        deltas = {k: after.get(k, 0) - before.get(k, 0)
                                  for k in wanted}
            print(f"adaptive-ab {label:16s} {best * 1e3:10.1f} ms  "
                  f"launches={int(deltas['shuffle.collective_launches'])} "
                  f"bytes_sent={int(deltas['shuffle.bytes_sent'])} "
                  f"broadcasts={int(deltas['plan.broadcast_joins'])} "
                  f"salted={int(deltas['plan.keys_salted'])}",
                  flush=True)

    # arm 1: fact x tiny dim — broadcast the dimension vs shuffle both
    dim_rows = max(64, n_rows >> 8)
    fact = {"k": r.integers(0, dim_rows, n_rows).astype(np.int32),
            "v": r.random(n_rows).astype(np.float32)}
    dim = {"k": np.arange(dim_rows, dtype=np.int32),
           "w": r.random(dim_rows).astype(np.float32)}
    ft = Table.from_numpy(list(fact), list(fact.values()), ctx=ctx)
    dt_ = Table.from_numpy(list(dim), list(dim.values()), ctx=ctx)
    qj = ft.plan().join(dt_, on="k", how="inner")
    run_arms(qj, (("broadcast", "1"), ("shuffle", "0")),
             {"CYLON_TPU_PLAN_BROADCAST_BYTES": str(64 << 20)})

    # arm 2: zipfian-key join + NUNIQUE (the Q10 shape) — salted
    # repartition vs plain.  The catalog is seeded OUTSIDE the timed
    # arms by one profiled adaptive-off run (the salt rule only fires
    # on OBSERVED skew; the shuffled join's output records it), and the
    # broadcast threshold is zeroed in both timed arms so the delta
    # below is the salt pipeline alone.
    zk = (np.minimum(r.zipf(1.3, n_rows), dim_rows) - 1).astype(np.int32)
    zt = Table.from_numpy(
        ["k", "u"], [zk, r.integers(0, 1 << 16, n_rows).astype(np.int64)],
        ctx=ctx)
    qs = (zt.plan().join(dt_, on="k", how="inner")
          .groupby(["l_k"], {"u": ["nunique"]}))
    with tempfile.TemporaryDirectory() as stats_dir:
        with config.knob_env(CYLON_TPU_PLAN="1",
                             CYLON_TPU_PLAN_ADAPTIVE="0",
                             CYLON_TPU_PROFILE="1",
                             CYLON_TPU_STATS_DIR=stats_dir):
            qs.execute()
        run_arms(qs, (("salted", "1"), ("plain", "0")),
                 {"CYLON_TPU_PLAN_SKEW_SALT": "1.2",
                  "CYLON_TPU_PLAN_BROADCAST_BYTES": "0",
                  "CYLON_TPU_STATS_DIR": stats_dir})
    print("done", flush=True)
    return True


if "--plan-ab" in sys.argv:
    _ok = _plan_ab(_POS_ARGS and int(_POS_ARGS[0]) or (1 << 20))
    if _ok and obs_spans.events_enabled():
        _tp, _mp = obs_export.export_all(prefix="microbench_plan_ab")
        print(f"trace artifact: {_tp}", flush=True)
    sys.exit(0 if _ok else 3)

if "--compress-ab" in sys.argv:
    _ok = _compress_ab(_POS_ARGS and int(_POS_ARGS[0]) or (1 << 20))
    if _ok and obs_spans.events_enabled():
        _tp, _mp = obs_export.export_all(prefix="microbench_compress_ab")
        print(f"trace artifact: {_tp}", flush=True)
    sys.exit(0 if _ok else 3)

if "--adaptive-ab" in sys.argv:
    _ok = _adaptive_ab(_POS_ARGS and int(_POS_ARGS[0]) or (1 << 18))
    if _ok and obs_spans.events_enabled():
        _tp, _mp = obs_export.export_all(prefix="microbench_adaptive_ab")
        print(f"trace artifact: {_tp}", flush=True)
    sys.exit(0 if _ok else 3)

rng = np.random.default_rng(5)
dev0 = jax.devices()[0]
print(f"backend={dev0.platform} kind={getattr(dev0, 'device_kind', dev0)} "
      f"n={N}", flush=True)

a = jnp.asarray(rng.integers(0, 1 << 30, N, dtype=np.int64).astype(np.uint32))
b = jnp.asarray(rng.integers(0, 1 << 30, N, dtype=np.int64).astype(np.uint32))
c = jnp.asarray(rng.random(N).astype(np.float32))
perm = jnp.asarray(rng.permutation(N).astype(np.int32))
sorted_idx = jnp.asarray(np.sort(rng.integers(0, N, N)).astype(np.int32))
seg = jnp.asarray(np.sort(rng.integers(0, N // 8 or 1, N)).astype(np.int32))


def timed(name, fn, *args, traffic_bytes=None):
    f = jax.jit(fn)
    try:
        with obs_spans.span("microbench.warm", op=name):
            out = f(*args)
            leaf = jax.tree_util.tree_leaves(out)[0]
            np.asarray(jax.device_get(leaf[:1]))  # force completion
        ts = []
        for _ in range(REPS):
            with obs_spans.span("microbench.rep", op=name):
                t0 = time.perf_counter()
                out = f(*args)
                leaf = jax.tree_util.tree_leaves(out)[0]
                np.asarray(jax.device_get(leaf[:1]))
                ts.append(time.perf_counter() - t0)
        ms = min(ts) * 1e3
        gbs = ""
        if traffic_bytes:
            gbs = f"{traffic_bytes / (ms / 1e3) / 1e9:8.1f} GB/s(min)"
        print(f"{name:36s} {ms:10.1f} ms {gbs}", flush=True)
    except Exception as e:
        print(f"{name:36s} FAILED: {type(e).__name__}: {str(e)[:160]}",
              flush=True)


B4 = 4 * N
timed("sort 1-op u32", lambda x: jax.lax.sort(x, is_stable=False), a,
      traffic_bytes=2 * B4)
timed("sort 2-op (1 key) u32", lambda x, y: jax.lax.sort(
    (x, y), num_keys=1, is_stable=False), a, b, traffic_bytes=4 * B4)
timed("sort 3-op (1 key)", lambda x, y, z: jax.lax.sort(
    (x, y, z), num_keys=1, is_stable=False), a, b, c,
    traffic_bytes=6 * B4)
timed("sort 2-op stable (2 keys)", lambda x, y: jax.lax.sort(
    (x, y), num_keys=2, is_stable=True), a, b, traffic_bytes=4 * B4)
timed("gather random (take)", lambda x, i: jnp.take(x, i), c, perm,
      traffic_bytes=3 * B4)
timed("gather sorted idx (take)", lambda x, i: jnp.take(x, i), c,
      sorted_idx, traffic_bytes=3 * B4)
timed("scatter-set permutation", lambda x, i: jnp.zeros_like(x).at[i].set(
    x, unique_indices=True, mode="promise_in_bounds"), c, perm,
    traffic_bytes=3 * B4)
timed("scatter-add segments", lambda x, i: jnp.zeros((N // 8 or 1,),
      jnp.float32).at[i].add(x), c, seg, traffic_bytes=3 * B4)
timed("segment_sum (jax.ops)", lambda x, i: jax.ops.segment_sum(
    x, i, N // 8 or 1), c, seg, traffic_bytes=3 * B4)
timed("cumsum f32", jnp.cumsum, c, traffic_bytes=2 * B4)
timed("cumsum i32", lambda x: jnp.cumsum(x.astype(jnp.int32)), a,
      traffic_bytes=2 * B4)
timed("cummax i32", lambda x: jax.lax.cummax(x.astype(jnp.int32)), a,
      traffic_bytes=2 * B4)
timed("associative_scan (sum,flag)", lambda x, f: jax.lax.associative_scan(
    lambda p, q: (jnp.where(q[1], q[0], p[0] + q[0]), p[1] | q[1]),
    (x, f)), c, a < (1 << 27), traffic_bytes=4 * B4)
timed("elementwise a*b+c", lambda x, y: x * y + 1.0, c, c,
      traffic_bytes=3 * B4)

# round-4b composite primitive (sort-realized permutation machinery), as
# this backend's row of ops/realization.py realizes it
from cylon_tpu.ops import compact  # noqa: E402

timed("count_leq_dense", lambda v: compact.count_leq_dense(v, N),
      jnp.sort(a.astype(jnp.int32) % N), traffic_bytes=4 * B4)

# the round-5 bet: two-sweep Pallas segmented scan vs the log-pass
# associative_scan above (same combine, same data) — keep-or-kill A/B
from cylon_tpu.ops import pallas_scan  # noqa: E402

flags = a < (1 << 27)
timed("pallas segmented_scan (sum,flag)",
      lambda x, f: pallas_scan.segmented_scan(x, f, "sum"), c, flags,
      traffic_bytes=6 * B4)
# 4 passes: sweep-1 read+write, then the (unfused) broadcast combine
# read+write — counted like segmented_scan's 6*B4 above
timed("pallas scan_1d cumsum f32",
      lambda x: pallas_scan.scan_1d(x, "sum"), c, traffic_bytes=4 * B4)
timed("pallas scan_1d cummin i32 rev",
      lambda x: pallas_scan.scan_1d(x.astype(jnp.int32), "min",
                                    reverse=True), a, traffic_bytes=4 * B4)

# ISSUE-2 tentpole: the packed-exchange plane's LOCAL cost — pack + one
# plane gather + unpack vs the 12 per-buffer gathers it replaces (6 data
# + 6 validity; the collective-launch saving itself needs a mesh —
# scaling_pack0/1 in the battery measures that).  6-column numeric
# schema, 5 plane words.
from cylon_tpu import column as colmod  # noqa: E402
from cylon_tpu.parallel import plane as plane_mod  # noqa: E402

cols6 = (
    colmod.from_numpy(np.asarray(a).view(np.int32)),
    colmod.from_numpy(np.asarray(c)),
    colmod.from_numpy((np.asarray(a) & 1).astype(bool)),
    colmod.from_numpy(np.asarray(a).astype(np.int8)),
    colmod.from_numpy(np.asarray(a).astype(np.int16)),
    colmod.from_numpy(np.asarray(c).astype(np.float64)),
)
ROW_B = 4 + 4 + 1 + 1 + 2 + 8 + 6  # data + validity bytes per row
W6 = plane_mod.plane_words(cols6)
live = jnp.asarray(np.arange(N) < int(N * 0.9))
timed(f"pack_plane 6-col ({W6} words)",
      lambda cs: plane_mod.pack_plane(cs), cols6,
      traffic_bytes=(ROW_B + 4 * W6) * N)
packed6 = jax.jit(plane_mod.pack_plane)(cols6)
timed("plane gather + unpack (packed)",
      lambda p, i, m, cs: plane_mod.unpack_plane(
          jnp.take(p, i, axis=0), cs, valid_mask=m),
      packed6, perm, live, cols6,
      traffic_bytes=(3 * 4 * W6 + ROW_B) * N)
timed("per-buffer gathers (12 buffers)",
      lambda cs, i, m: tuple(col.take(i, valid_mask=m) for col in cs),
      cols6, perm, live, traffic_bytes=(2 * ROW_B + 4 * len(cols6)) * N)
# ISSUE-4: emit the trace artifact beside the numbers when event tracing
# is on (CYLON_TPU_TRACE=1) so a regression hunt can open the Perfetto
# view of the exact run that produced the table above
if obs_spans.events_enabled():
    _tp, _mp = obs_export.export_all(prefix="microbench")
    print(f"trace artifact: {_tp}", flush=True)
    print(f"metrics artifact: {_mp}", flush=True)
print("done", flush=True)
