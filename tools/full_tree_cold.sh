#!/bin/bash
# Reproduce the XLA:CPU full-tree compiler segfault: the WHOLE test tree
# (fast+slow, one process, compile cache disabled) with faulthandler so
# the crash point and native trace are captured.  Usage:
#   tools/full_tree_cold.sh [outfile]
# Exit 0 = no crash (suite green); 139/134 = the repro, with the dying
# test visible at the tail of the log.
set -u
cd "$(dirname "$0")/.."
OUT=${1:-/tmp/full_tree_cold.log}
# static-analysis gate first: trace-safety rules, the Level-3
# concurrency rules (CY113/CY114/CY115) + the jaxpr collective budgets
# are pure-CPU and catch a 1 -> 13 collective regression in seconds,
# before the 4-hour tree gets a chance to; --lockgraph additionally
# drives one elastic + one router smoke under the runtime lock
# recorder and fails on any observed lock-order edge missing from the
# committed golden (regenerate with --write-lockgraph after review)
env JAX_PLATFORMS=cpu \
    python -m cylon_tpu.analysis cylon_tpu --budgets --lockgraph || {
  rc=$?
  echo "cylint failed (rc=$rc); fix findings before the full tree" >&2
  exit $rc
}
# trace smoke (ISSUE-4): one small world-4 distributed join with event
# tracing on must export a Perfetto/Chrome-trace artifact that loads and
# carries the exchange spans — catches an obs wiring regression in
# seconds, before the full tree runs
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    CYLON_TPU_TRACE=1 CYLON_TPU_TRACE_DIR=/tmp/cylon_trace_smoke \
    python - <<'PYEOF'
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from cylon_tpu import Table
from cylon_tpu.context import CylonContext, TPUConfig
from cylon_tpu.obs import export, metrics, spans
ctx = CylonContext.InitDistributed(TPUConfig(world_size=4))
n = 128
t = Table.from_numpy(["k", "v"], [np.arange(n, dtype=np.int32) % 17,
                                  np.arange(n, dtype=np.float32)],
                     ctx=ctx, capacity=n)
j = t.distributed_join(t, on="k")
assert j.row_count > 0
tp, mp = export.export_all(prefix="smoke")
doc = export.load_trace(tp)
names = {e["name"] for e in doc["traceEvents"]}
assert "shuffle.exchange" in names and "table.distributed_join" in names, names
assert metrics.snapshot()["counters"]["shuffle.collective_launches"] > 0
print(f"trace smoke ok: {tp} ({len(doc['traceEvents'])} events)")
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "trace smoke failed (rc=$rc); fix obs wiring before the full tree" >&2
  exit $rc
fi
# crash-resume smoke (ISSUE-5): a journaled run killed hard (os._exit at
# the manifest-commit fault point) must resume bit-identically from a
# fresh process, re-executing only the unfinished passes — catches a
# durable-execution regression in ~30 s, before the full tree runs
DJ=$(mktemp -d /tmp/cylon_durable_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    CYLON_TPU_DURABLE_DIR="$DJ/journal" \
    CYLON_TPU_FAULT_PLAN='journal_commit@2=killhard' \
    python -m tests.durable_worker "$DJ/killed.npz" "$DJ/killed.json" \
    >/dev/null 2>&1
krc=$?
if [ $krc -ne 137 ]; then
  echo "crash-resume smoke: killhard run exited $krc (expected 137)" >&2
  rm -rf "$DJ"; exit 1
fi
env JAX_PLATFORMS=cpu \
    CYLON_TPU_DURABLE_DIR="$DJ/journal" \
    python -m tests.durable_worker "$DJ/resumed.npz" "$DJ/resumed.json" \
  && env JAX_PLATFORMS=cpu \
    python -m tests.durable_worker "$DJ/base.npz" "$DJ/base.json" \
  && python - "$DJ" <<'PYEOF'
import json, sys
import numpy as np
d = sys.argv[1]
stats = json.load(open(f"{d}/resumed.json"))
assert stats["passes_skipped"] == 1, stats   # 1 pass committed pre-kill
assert stats["parts_run"] == stats["passes"] - 1, stats
r = np.load(f"{d}/resumed.npz"); b = np.load(f"{d}/base.npz")
assert set(r.files) == set(b.files)
for f in b.files:
    assert r[f].dtype == b[f].dtype, f
    np.testing.assert_array_equal(r[f], b[f], err_msg=f)
print(f"crash-resume smoke ok: skipped {stats['passes_skipped']}, "
      f"re-ran {stats['parts_run']} of {stats['passes']} passes")
PYEOF
rc=$?
rm -rf "$DJ"
if [ $rc -ne 0 ]; then
  echo "crash-resume smoke failed (rc=$rc); fix durable journaling before the full tree" >&2
  exit $rc
fi
# elastic kill-one-resume smoke (ISSUE-6): a 2-process gang with rank 1
# killed (rank_kill = os._exit(137)) at its first pass boundary must
# shrink to the survivor, which finishes the run and assembles the full
# result from the shared journal — catches a membership/journal
# regression in ~30 s, before the full tree runs
env JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import json, os, subprocess, sys, tempfile

sys.path.insert(0, os.getcwd())
from cylon_tpu import elastic

td = tempfile.mkdtemp(prefix="cylon_elastic_smoke.")
coord = elastic.Coordinator(2, heartbeat_timeout_s=0.8).start()
addr = f"{coord.address[0]}:{coord.address[1]}"
base_env = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS",
                         "CYLON_TPU_FAULT_PLAN", "CYLON_TPU_DURABLE_DIR")}
base_env.update(CYLON_TPU_DURABLE_DIR=os.path.join(td, "journal"),
                CYLON_TPU_HEARTBEAT_S="0.1",
                CYLON_TPU_HEARTBEAT_TIMEOUT_S="0.8")
procs = []
for r in range(2):
    env = dict(base_env)
    if r == 1:
        env["CYLON_TPU_FAULT_PLAN"] = "elastic.pass.r1@1=rank_kill"
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "tests.elastic_worker", str(r), "2", addr,
         os.path.join(td, f"out_r{r}.npz"),
         os.path.join(td, f"stats_r{r}.json")], env=env))
try:
    for p in procs:
        p.wait(timeout=240)
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
    coord.stop()
assert procs[1].returncode == 137, procs[1].returncode
assert procs[0].returncode == 0, procs[0].returncode
stats = json.load(open(os.path.join(td, "stats_r0.json")))
assert stats["passes_skipped"] == stats["passes"], stats
assert stats["epoch"] >= 1 and stats["members"] == [0], stats
print(f"elastic kill-one-resume smoke ok: survivor assembled "
      f"{stats['passes']} journaled passes at epoch {stats['epoch']}")
import shutil; shutil.rmtree(td, ignore_errors=True)
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "elastic kill-one-resume smoke failed (rc=$rc); fix elastic membership before the full tree" >&2
  exit $rc
fi
# coordinator-restart chaos smoke (ISSUE-11): a 3-process gang whose
# coordinator is killed mid-pass and restarted from the durable
# COORD_LOG at the same address — every worker must ride through its
# reconnect window (incarnation 1 observed, epoch bumped once), resume
# via the journal, and assemble a result bit-identical to the
# single-process oracle; asserted from the artifact JSON — catches a
# control-plane survivability regression in ~60 s, before the full tree
env JAX_PLATFORMS=cpu \
    python - <<'PYEOF'
import json, os, subprocess, sys, tempfile, time

sys.path.insert(0, os.getcwd())
import numpy as np
from cylon_tpu import elastic
from tests.elastic_worker import N_PASSES, inputs, run_op

td = tempfile.mkdtemp(prefix="cylon_restart_smoke.")
left, right = inputs(13)
base, _ = run_op(left, right)
order = np.argsort(base["l_k"], kind="stable")
expected = {k: np.asarray(v)[order] for k, v in base.items()}

coord_dir = os.path.join(td, "coord")
coord = elastic.Coordinator(3, heartbeat_timeout_s=2.5,
                            log_dir=coord_dir).start()
base_env = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS",
                         "CYLON_TPU_FAULT_PLAN", "CYLON_TPU_DURABLE_DIR")}
base_env.update(CYLON_TPU_DURABLE_DIR=os.path.join(td, "journal"),
                CYLON_TPU_HEARTBEAT_S="0.1",
                CYLON_TPU_HEARTBEAT_TIMEOUT_S="0.8",
                CYLON_TPU_COORD_RECONNECT_S="30")
addr = f"{coord.address[0]}:{coord.address[1]}"
procs = [subprocess.Popen(
    [sys.executable, "-m", "tests.elastic_worker", str(r), "3", addr,
     os.path.join(td, f"out_r{r}.npz"),
     os.path.join(td, f"stats_r{r}.json"), "13"],
    env=dict(base_env)) for r in range(3)]
coord2 = None
try:
    deadline = time.monotonic() + 60
    while len(coord.view().members) < 3:
        assert time.monotonic() < deadline, "gang never formed"
        time.sleep(0.05)
    time.sleep(0.3)            # let the run get under way
    host, port = coord.address
    coord.stop()               # kill -9 semantics: no goodbye
    time.sleep(1.0)            # workers enter their reconnect windows
    coord2 = elastic.Coordinator(3, heartbeat_timeout_s=2.5,
                                 log_dir=coord_dir, host=host,
                                 port=port).start()
    assert coord2.restored and coord2.incarnation == 1, coord2.incarnation
    for p in procs:
        p.wait(timeout=240)
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
    coord.stop()
    if coord2 is not None:
        coord2.stop()
for r in range(3):
    assert procs[r].returncode == 0, (r, procs[r].returncode)
    got = dict(np.load(os.path.join(td, f"out_r{r}.npz"),
                       allow_pickle=True))
    for k in expected:
        assert got[k].dtype == expected[k].dtype, k
        np.testing.assert_array_equal(got[k], expected[k], err_msg=k)
    stats = json.load(open(os.path.join(td, f"stats_r{r}.json")))
    assert stats["incarnation"] == 1, stats
    assert stats["epoch"] >= 1, stats
    assert stats["passes_skipped"] == N_PASSES, stats
print(f"coordinator-restart smoke ok: 3 workers rode through the "
      f"restart (incarnation 1), bit-identical to oracle, "
      f"{N_PASSES} journaled passes")
import shutil; shutil.rmtree(td, ignore_errors=True)
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "coordinator-restart smoke failed (rc=$rc); fix the survivable control plane before the full tree" >&2
  exit $rc
fi
# fleet-observability smoke (ISSUE-8): a 2-process elastic run with a
# heartbeat_loss straggler (rank 1 goes silent AND drags a seeded delay)
# must leave per-rank clock-aligned traces that trace_merge combines
# into one schema-valid timeline with nonzero cross-rank skew, plus a
# flight-recorder dump for the fenced rank and a rank-loss dump from the
# coordinator — with CYLON_TPU_TRACE only armed for the workers, never
# needed for the flight dumps
FT=$(mktemp -d /tmp/cylon_fleet_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    CYLON_TPU_TRACE_DIR="$FT/traces" \
    python - "$FT" <<'PYEOF'
import json, os, subprocess, sys, tempfile

sys.path.insert(0, os.getcwd())
from cylon_tpu import elastic

td = sys.argv[1]
coord = elastic.Coordinator(2, heartbeat_timeout_s=0.8).start()
addr = f"{coord.address[0]}:{coord.address[1]}"
base_env = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS",
                         "CYLON_TPU_FAULT_PLAN", "CYLON_TPU_DURABLE_DIR")}
base_env.update(CYLON_TPU_DURABLE_DIR=os.path.join(td, "journal"),
                CYLON_TPU_HEARTBEAT_S="0.1",
                CYLON_TPU_HEARTBEAT_TIMEOUT_S="0.8",
                CYLON_TPU_TRACE="1",
                CYLON_TPU_TRACE_DIR=os.path.join(td, "traces"))
procs = []
for r in range(2):
    env = dict(base_env)
    if r == 1:
        # silent straggler + seeded per-pass delay: fenced, late, traced
        env["CYLON_TPU_FAULT_PLAN"] = \
            "elastic.heartbeat.r1@2=heartbeat_loss;elastic.pass.r1@1+=delay"
        env["CYLON_TPU_FAULT_DELAY_S"] = "1.0"
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "tests.elastic_worker", str(r), "2", addr,
         os.path.join(td, f"out_r{r}.npz"),
         os.path.join(td, f"stats_r{r}.json")], env=env))
try:
    for p in procs:
        p.wait(timeout=240)
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
    coord.stop()
assert procs[0].returncode == 0, procs[0].returncode
assert procs[1].returncode == 4, procs[1].returncode  # fenced straggler
# the coordinator (this process) dumped the rank loss
flight = os.path.join(td, "traces", "flight")
dumps = os.listdir(flight)
assert any(f.endswith(".rcoord.json") for f in dumps), dumps
# the fenced rank dumped its own post-mortem, run-id namespaced
fenced = json.load(open(os.path.join(flight, "seed7.r1.json")))
assert fenced["kind"] == "cylon_tpu.flight", fenced["kind"]
assert fenced["reason"] == "fenced", fenced["reason"]
assert fenced["rank"] == 1 and fenced["traceEvents"], "empty fenced dump"
print(f"fleet smoke: workers ok (r0=0, r1=fenced), "
      f"flight dumps: {sorted(dumps)}")
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "fleet obs smoke (run) failed (rc=$rc); fix fleet observability before the full tree" >&2
  rm -rf "$FT"; exit $rc
fi
env JAX_PLATFORMS=cpu \
    python tools/trace_merge.py "$FT/traces" -o "$FT/merged.json" --json \
    > "$FT/merge_summary.json" \
  && python - "$FT" <<'PYEOF'
import json, sys
td = sys.argv[1]
summary = json.load(open(f"{td}/merge_summary.json"))
assert summary["ranks"] == [0, 1], summary["ranks"]
assert summary["aligned"] is True, summary
assert summary["dropped_events"] == 0, summary
# merged file re-validates against the Chrome-trace schema
merged = json.load(open(f"{td}/merged.json"))
for e in merged["traceEvents"]:
    if e["ph"] == "M":
        continue
    assert all(k in e for k in ("name", "ph", "ts", "pid", "tid")), e
    assert e["ph"] != "X" or "dur" in e, e
# nonzero cross-rank skew on the run's rendezvous (both ranks arrived
# at the epoch-0 start barrier before the straggler was fenced)
rows = [r for r in summary["collectives"] if len(r["ranks"]) == 2]
assert rows, summary["collectives"]
assert any(r["skew_us"] > 0 for r in rows), rows
print(f"fleet smoke ok: merged {len(merged['traceEvents'])} events, "
      f"{len(rows)} cross-rank collective(s), "
      f"max skew {max(r['skew_us'] for r in rows) / 1e3:.3f}ms")
PYEOF
rc=$?
rm -rf "$FT"
if [ $rc -ne 0 ]; then
  echo "fleet obs smoke (merge) failed (rc=$rc); fix trace_merge before the full tree" >&2
  exit $rc
fi
# causal-tracing smoke (ISSUE-13): ONE serve request on rank 0 drives a
# 3-process elastic gang with a seeded per-pass delay on rank 2; the
# request's traceparent must propagate over the coordinator wire so the
# merged trace carries ONE trace_id across all three ranks, and
# tools/critical_path.py must attribute >=90% of the request wall and
# name the delayed rank as the dominant path segment
CT=$(mktemp -d /tmp/cylon_ctrace_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    python - "$CT" <<'PYEOF'
import json, os, subprocess, sys

sys.path.insert(0, os.getcwd())
from cylon_tpu import elastic

td = sys.argv[1]
coord = elastic.Coordinator(3, heartbeat_timeout_s=2.5).start()
addr = f"{coord.address[0]}:{coord.address[1]}"
base_env = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS",
                         "CYLON_TPU_FAULT_PLAN", "CYLON_TPU_DURABLE_DIR")}
base_env.update(CYLON_TPU_DURABLE_DIR=os.path.join(td, "journal"),
                CYLON_TPU_HEARTBEAT_S="0.1",
                CYLON_TPU_HEARTBEAT_TIMEOUT_S="2.5",
                CYLON_TPU_TRACE="1",
                CYLON_TPU_TRACE_DIR=os.path.join(td, "traces"))
procs = []
for r in range(3):
    env = dict(base_env)
    if r == 2:
        # the seeded straggler: 3.5s sleep at every pass boundary —
        # large enough to dominate any warmed host-side work block
        env["CYLON_TPU_FAULT_PLAN"] = "elastic.pass.r2@1+=delay"
        env["CYLON_TPU_FAULT_DELAY_S"] = "3.5"
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "tests.trace_worker", str(r), "3", addr,
         os.path.join(td, f"out_r{r}.npz"),
         os.path.join(td, f"stats_r{r}.json")], env=env))
try:
    for p in procs:
        p.wait(timeout=360)
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
    coord.stop()
for r, p in enumerate(procs):
    assert p.returncode == 0, (r, p.returncode)
st = json.load(open(os.path.join(td, "stats_r0.json")))
assert st["state"] == "done" and st["trace_id"], st
print(f"tracing smoke: request {st['trace_id']} served in "
      f"{st['duration_s']:.1f}s across 3 ranks")
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "causal tracing smoke (run) failed (rc=$rc); fix trace propagation before the full tree" >&2
  rm -rf "$CT"; exit $rc
fi
env JAX_PLATFORMS=cpu \
    python tools/trace_merge.py "$CT/traces" -o "$CT/merged.json" --json \
    > "$CT/merge_summary.json" \
  && python - "$CT" <<'PYEOF'
import json, sys
td = sys.argv[1]
summary = json.load(open(f"{td}/merge_summary.json"))
assert summary["ranks"] == [0, 1, 2], summary["ranks"]
assert summary["aligned"] is True, summary
st = json.load(open(f"{td}/stats_r0.json"))
cp = summary["critical_path"]
assert cp is not None, "no critical path in merge summary"
# ONE request trace: the serve-minted id, rooted at serve.request,
# carried by spans on EVERY rank of the gang
assert cp["trace_id"] == st["trace_id"], (cp["trace_id"], st["trace_id"])
assert cp["root"]["name"] == "serve.request", cp["root"]
merged = json.load(open(f"{td}/merged.json"))
pids = sorted({e["pid"] for e in merged["traceEvents"]
               if (e.get("args") or {}).get("trace_id") == cp["trace_id"]})
assert pids == [0, 1, 2], f"trace does not span all ranks: {pids}"
# the walk accounts for >=90% of the request wall, and the seeded-delay
# rank owns the dominant path segment
assert cp["coverage"] >= 0.9, cp["coverage"]
assert cp["dominant"]["rank"] == 2, cp["dominant"]
print(f"tracing smoke ok: trace {cp['trace_id'][:16]}... spans ranks "
      f"{pids}, coverage {100 * cp['coverage']:.1f}%, dominant segment "
      f"{cp['dominant']['name']} on rank {cp['dominant']['rank']} "
      f"({cp['dominant']['dur_us'] / 1e6:.1f}s)")
PYEOF
rc=$?
rm -rf "$CT"
if [ $rc -ne 0 ]; then
  echo "causal tracing smoke (merge/critical-path) failed (rc=$rc); fix critical_path before the full tree" >&2
  exit $rc
fi
# serve smoke (ISSUE-7): flood a 2-tenant query service against a
# single-slot admission queue — overload must resolve as classified
# sheds + exact serves (never a hang), and a repeated query must hit
# the journal result cache; counts asserted from the artifact JSON —
# catches an admission/cache regression in ~30 s, before the full tree
SJ=$(mktemp -d /tmp/cylon_serve_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    CYLON_TPU_DURABLE_DIR="$SJ/journal" \
    python - "$SJ" <<'PYEOF'
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from cylon_tpu.serve import QueryService
from cylon_tpu.status import CylonError, Code
from cylon_tpu.exec import chunked_join

td = sys.argv[1]
rng = np.random.default_rng(7)
def mk(seed):
    r = np.random.default_rng(seed)
    n = 1200
    return ({"k": r.integers(0, n, n).astype(np.int64),
             "a": r.random(n).astype(np.float32)},
            {"k": r.integers(0, n, n).astype(np.int64),
             "b": r.random(n).astype(np.float32)})
inputs = {"tenant-a": mk(1), "tenant-b": mk(2)}
oracle = {t: chunked_join(l, r, on="k", passes=2, mode="hash")[0]
          for t, (l, r) in inputs.items()}
svc = QueryService(queue_cap=1)
admitted, shed = [], 0
for _ in range(5):
    for t, (l, r) in inputs.items():
        try:
            admitted.append((t, svc.submit(t, "join", l, r, on="k",
                                           passes=2, mode="hash")))
        except CylonError as e:
            assert e.code in (Code.ResourceExhausted, Code.Unavailable), e
            shed += 1
for t, ticket in admitted:
    res, _ = ticket.result(timeout=180)
    for k in oracle[t]:
        np.testing.assert_array_equal(res[k], oracle[t][k])
# repeated fingerprint: the journal serves it with zero device passes
ca, cb = inputs["tenant-a"]
hit = svc.submit("tenant-a", "join", ca, cb, on="k", passes=2, mode="hash")
hit.result(timeout=180)
stats = svc.stats()
svc.close()
with open(f"{td}/serve_smoke.json", "w") as fh:
    json.dump(stats, fh, indent=1, sort_keys=True)
assert stats["shed"] == shed and shed > 0, stats
assert stats["completed"] == len(admitted) + 1, stats
assert stats["failed"] == 0, stats
assert stats["cache_hits"] >= 1, stats
print(f"serve smoke ok: admitted={stats['admitted']} shed={stats['shed']} "
      f"cache_hits={stats['cache_hits']} "
      f"artifact={td}/serve_smoke.json")
PYEOF
rc=$?
rm -rf "$SJ"
if [ $rc -ne 0 ]; then
  echo "serve smoke failed (rc=$rc); fix the query service before the full tree" >&2
  exit $rc
fi
# router smoke (ISSUE-14): a QueryRouter fronting 2 replica worker
# PROCESSES sharing one durable journal, flooded by 12 traced requests
# while a seeded rank_kill takes replica 1 down at its 2nd dispatch —
# asserts the re-route counter >= 1, fleet served == submitted minus
# classified sheds (zero hangs, zero unclassified failures), a repeated
# fingerprint served as a cache hit on the survivor, and ONE trace_id
# spanning router + both replicas in the merged timeline (the killed
# replica exports incrementally, so its spans survive os._exit)
RT=$(mktemp -d /tmp/cylon_router_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    CYLON_TPU_TRACE=1 CYLON_TPU_TRACE_DIR="$RT/traces" \
    CYLON_TPU_DURABLE_DIR="$RT/journal" \
    python - "$RT" <<'PYEOF'
import json, os, subprocess, sys, threading, time

sys.path.insert(0, os.getcwd())
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from cylon_tpu import elastic
from cylon_tpu.obs import export, metrics as obs_metrics, tracectx
from cylon_tpu.router import QueryRouter, RouterClient
from cylon_tpu.status import Code, CylonError

td = sys.argv[1]
router = QueryRouter(world=3, heartbeat_timeout_s=2.5).start()
addr = f"{router.address[0]}:{router.address[1]}"
base_env = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS",
                         "CYLON_TPU_FAULT_PLAN")}
base_env.update(CYLON_TPU_HEARTBEAT_S="0.1",
                CYLON_TPU_HEARTBEAT_TIMEOUT_S="2.5",
                CYLON_TPU_COORD_RECONNECT_S="0")
procs = []
for r in range(2):
    env = dict(base_env)
    if r == 1:
        env["CYLON_TPU_FAULT_PLAN"] = "router.pass.r1@2=rank_kill"
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "tests.router_worker", str(r), "3", addr],
        env=env))
try:
    agent = elastic.Agent(addr, 2, interval_s=0.1, timeout_s=2.5,
                          reconnect_s=0.0).start()
    deadline = time.monotonic() + 120
    while router.router_status()["replicas_live"] < 2:
        assert time.monotonic() < deadline, "replicas never registered"
        time.sleep(0.1)
    cli = RouterClient(addr)
    def mk(seed):
        r = np.random.default_rng(seed)
        n = 1200
        return ({"k": r.integers(0, n, n).astype(np.int64),
                 "a": r.random(n).astype(np.float32)},
                {"k": r.integers(0, n, n).astype(np.int64),
                 "b": r.random(n).astype(np.float32)})
    inputs = [mk(100 + i) for i in range(4)]
    root = tracectx.new_trace()
    served, errs, lock = [], [], threading.Lock()
    def one(i):
        l, r = inputs[i % 4]
        with tracectx.activate(root):
            try:
                res, stats = cli.route(f"tenant-{i % 4}", "kjoin", l, r,
                                       on="k", passes=2, mode="hash",
                                       timeout_s=300)
                with lock:
                    served.append((i, stats))
            except CylonError as e:
                with lock:
                    errs.append((i, e))
    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(360)
    assert all(not t.is_alive() for t in threads), "a routed request hung"
    for i, e in errs:
        assert e.code in (Code.ResourceExhausted, Code.Unavailable,
                          Code.Timeout), (i, e)
    assert len(served) + len(errs) == 12
    rr = obs_metrics.counter_value("router.reroutes")
    assert rr >= 1, f"no re-route observed (reroutes={rr})"
    st = router.router_status()
    assert st["routed"] == len(served), (st, len(served))
    # the repeated fingerprint: a cache hit on the SURVIVOR, served
    # from the shared journal no matter which replica executed it
    l, r = inputs[0]
    with tracectx.activate(root):
        res, stats = cli.route("tenant-0", "kjoin", l, r, on="k",
                               passes=2, mode="hash", timeout_s=300)
    assert stats["router"]["replica"] == 0, stats["router"]
    assert stats["router"]["cache_hit"] is True, stats["router"]
    export.export_trace(rank=2)
    with open(f"{td}/summary.json", "w") as fh:
        json.dump({"trace_id": root.trace_id, "served": len(served),
                   "sheds": len(errs), "reroutes": rr,
                   "router": st}, fh, indent=1, sort_keys=True)
finally:
    router.stop()
    for p in procs:
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
assert procs[0].returncode == 0, procs[0].returncode
assert procs[1].returncode == 137, procs[1].returncode
print(f"router smoke: {len(served)}/12 served, {len(errs)} classified "
      f"shed(s), {int(rr)} reroute(s), repeat = cache hit on survivor")
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "router smoke (run) failed (rc=$rc); fix the query router before the full tree" >&2
  rm -rf "$RT"; exit $rc
fi
env JAX_PLATFORMS=cpu \
    python tools/trace_merge.py "$RT/traces" -o "$RT/merged.json" --json \
    > "$RT/merge_summary.json" \
  && python - "$RT" <<'PYEOF'
import json, sys
td = sys.argv[1]
summary = json.load(open(f"{td}/merge_summary.json"))
assert summary["aligned"] is True, summary
root = json.load(open(f"{td}/summary.json"))["trace_id"]
merged = json.load(open(f"{td}/merged.json"))
pids = sorted({e["pid"] for e in merged["traceEvents"]
               if (e.get("args") or {}).get("trace_id") == root})
# ONE causally-linked trace through the extra hop: the router (rank 2)
# and BOTH replicas — including the killed one, whose incremental
# exports preserved its completed-request spans
assert pids == [0, 1, 2], f"trace does not span router+replicas: {pids}"
print(f"router smoke ok: trace {root[:16]}... spans router + both "
      f"replicas (pids {pids}) in the merged timeline")
PYEOF
rc=$?
rm -rf "$RT"
if [ $rc -ne 0 ]; then
  echo "router smoke (merge) failed (rc=$rc); fix router trace propagation before the full tree" >&2
  exit $rc
fi
# tail-tolerance chaos smoke (ISSUE-16): the same 2-replica fleet, but
# replica 1 is seeded SICK (3s dispatch stalls) instead of killed, with
# hedging + health breakers armed — the 12-request flood must complete
# bit-identical to the oracle with >=1 hedge fired/won/loser-cancelled,
# replica 1's breaker must OPEN under the stalls and RECOVER via a
# half-open probe once the stalls are exhausted; all asserted from the
# artifact JSON
HT=$(mktemp -d /tmp/cylon_hedge_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    CYLON_TPU_ROUTER_HEDGE_MS=200 \
    CYLON_TPU_ROUTER_BREAKER_FAILURES=2 \
    CYLON_TPU_ROUTER_BREAKER_COOLDOWN_S=1.5 \
    python - "$HT" <<'PYEOF'
import json, os, subprocess, sys, threading, time

sys.path.insert(0, os.getcwd())
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from cylon_tpu import elastic
from cylon_tpu.exec import chunked_join
from cylon_tpu.router import QueryRouter, RouterClient
from cylon_tpu.status import CylonError

td = sys.argv[1]
router = QueryRouter(world=3, heartbeat_timeout_s=2.5).start()
addr = f"{router.address[0]}:{router.address[1]}"
base_env = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS",
                         "CYLON_TPU_FAULT_PLAN")}
# the shared journal is the WORKERS' cache: the driver computes its
# oracles journal-off, so the flood replays nothing pre-seeded
base_env.update(CYLON_TPU_HEARTBEAT_S="0.1",
                CYLON_TPU_HEARTBEAT_TIMEOUT_S="2.5",
                CYLON_TPU_COORD_RECONNECT_S="0",
                CYLON_TPU_DURABLE_DIR=os.path.join(td, "journal"))
procs = []
for r in range(2):
    env = dict(base_env)
    if r == 1:
        env["CYLON_TPU_FAULT_PLAN"] = ("router.pass.r1@1=replica_sick;"
                                       "router.pass.r1@2=replica_sick")
        env["CYLON_TPU_FAULT_DELAY_S"] = "3"
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "tests.router_worker", str(r), "3", addr],
        env=env))
try:
    agent = elastic.Agent(addr, 2, interval_s=0.1, timeout_s=2.5,
                          reconnect_s=0.0).start()
    deadline = time.monotonic() + 120
    while router.router_status()["replicas_live"] < 2:
        assert time.monotonic() < deadline, "replicas never registered"
        time.sleep(0.1)
    cli = RouterClient(addr)
    def mk(seed):
        rg = np.random.default_rng(seed)
        n = 1200
        return ({"k": rg.integers(0, n, n).astype(np.int64),
                 "a": rg.random(n).astype(np.float32)},
                {"k": rg.integers(0, n, n).astype(np.int64),
                 "b": rg.random(n).astype(np.float32)})
    inputs = [mk(200 + i) for i in range(4)]
    oracles = [chunked_join(l, r, on="k", passes=2, mode="hash")[0]
               for l, r in inputs]
    outs, errs, lock = {}, [], threading.Lock()
    def one(i):
        l, r = inputs[i % 4]
        try:
            res, stats = cli.route(f"tenant-{i % 4}", "kjoin", l, r,
                                   on="k", passes=2, mode="hash",
                                   timeout_s=300)
            with lock:
                outs[i] = res
        except CylonError as e:
            with lock:
                errs.append((i, e))
    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(12)]
    for t in threads:
        t.start()
        time.sleep(0.05)
    for t in threads:
        t.join(360)
    assert all(not t.is_alive() for t in threads), "a routed request hung"
    assert not errs, errs  # a SICK replica only stalls, nothing may fail
    for i, res in outs.items():
        base = oracles[i % 4]
        assert set(res) == set(base), i
        for k in res:
            np.testing.assert_array_equal(np.asarray(res[k]),
                                          np.asarray(base[k]), err_msg=k)
    # ride-through: once the seeded stalls are exhausted, a half-open
    # probe must re-close replica 1's breaker
    deadline = time.monotonic() + 90
    while router.router_status()["breakers"].get("1") != "closed":
        assert time.monotonic() < deadline, "breaker never re-closed"
        l, r = inputs[0]
        try:
            cli.route("tenant-0", "kjoin", l, r, on="k", passes=2,
                      mode="hash", timeout_s=300)
        except CylonError:
            pass
        time.sleep(0.3)
    st = router.router_status()
    with open(f"{td}/summary.json", "w") as fh:
        json.dump({"served": len(outs), "router": st}, fh, indent=1,
                  sort_keys=True)
finally:
    router.stop()
    for p in procs:
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
assert procs[0].returncode == 0, procs[0].returncode
assert procs[1].returncode == 0, procs[1].returncode
print(f"tail-tolerance smoke: 12/12 bit-identical under a sick replica "
      f"(hedges fired={st['hedges_fired']} won={st['hedges_won']})")
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "tail-tolerance smoke (run) failed (rc=$rc); fix hedging/breakers before the full tree" >&2
  rm -rf "$HT"; exit $rc
fi
python - "$HT" <<'PYEOF'
import json, sys
td = sys.argv[1]
s = json.load(open(f"{td}/summary.json"))
rt = s["router"]
r1 = rt["replicas"]["1"]
assert s["served"] == 12, s
assert rt["hedges_fired"] >= 1, rt
assert rt["hedges_won"] >= 1, rt
assert rt["hedges_lost_cancelled"] >= 1, rt
assert r1["hedged_away"] >= 1, r1
assert r1["breaker_opens"] >= 1, r1
assert r1["breaker_probes"] >= 1, r1
assert rt["breakers"]["1"] == "closed", rt
print(f"tail-tolerance smoke ok: hedges fired={rt['hedges_fired']} "
      f"won={rt['hedges_won']} cancelled={rt['hedges_lost_cancelled']}; "
      f"replica 1 breaker opened {r1['breaker_opens']}x, re-closed "
      f"after {r1['breaker_probes']} probe(s)")
PYEOF
rc=$?
rm -rf "$HT"
if [ $rc -ne 0 ]; then
  echo "tail-tolerance smoke (artifact) failed (rc=$rc); fix hedging/breakers before the full tree" >&2
  exit $rc
fi
# planner smoke (ISSUE-9): TPC-H Q10 (4-way join) through the logical
# planner on the world-8 CPU mesh — the artifact JSON must record at
# least one elided shuffle and the planned result must be bit-identical
# to the eager per-op execution of the same query (compare_eager
# asserts column-by-column exact equality inside the example) — catches
# an optimizer/executor regression in ~2 min, before the full tree
PT=$(mktemp -d /tmp/cylon_plan_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - "$PT" <<'PYEOF'
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from examples import tpch_q10

rec = tpch_q10.run(sf=0.004, check=True, compare_eager=True)
with open(f"{sys.argv[1]}/tpch_q10.json", "w") as fh:
    json.dump(rec, fh, indent=1, sort_keys=True)
PYEOF
rc=$?
if [ $rc -eq 0 ]; then
  python - "$PT" <<'PYEOF'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/tpch_q10.json"))
assert rec["shuffles_elided"] >= 1, rec
assert rec["eager_bit_identical"] is True, rec
assert rec["top"] == 20, rec
print(f"planner smoke ok: q10 elided {rec['shuffles_elided']} shuffle(s), "
      f"bit-identical to eager, top-{rec['top']} matches pandas")
PYEOF
  rc=$?
fi
rm -rf "$PT"
if [ $rc -ne 0 ]; then
  echo "planner smoke failed (rc=$rc); fix the query planner before the full tree" >&2
  exit $rc
fi
# compression smoke (ISSUE-10): a low-cardinality TPC-H Q3 lineitem
# shuffle with CYLON_TPU_SHUFFLE_COMPRESS on vs off must drop
# shuffle.bytes_sent by >1.5x while the shards stay bit-identical —
# asserted from the artifact JSON, catches a payload-encoder regression
# in ~1 min, before the full tree runs
CS=$(mktemp -d /tmp/cylon_compress_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - "$CS" <<'PYEOF'
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from cylon_tpu import Table, config
from cylon_tpu.context import CylonContext, TPUConfig
from cylon_tpu.obs import metrics

ctx = CylonContext.InitDistributed(TPUConfig(world_size=4))
rng = np.random.default_rng(0)
from examples import tpch_data
raw_o = tpch_data.orders(0.004, rng, q3_cols=True)
raw_l = tpch_data.lineitem(0.004, rng, q5_keys=True,
                           orders_rows=len(raw_o["o_orderkey"]))
raw_l.pop("l_suppkey", None)
line = Table.from_numpy(list(raw_l), list(raw_l.values()), ctx=ctx)

def shards(t):
    out = []
    for sid, cols, cnt in t._addressable_host_shards():
        out.append((sid, cnt, [(np.asarray(c.data)[:cnt],
                                np.asarray(c.validity)[:cnt],
                                None if c.lengths is None
                                else np.asarray(c.lengths)[:cnt])
                               for c in cols]))
    return out

res = {}
for label, mode in (("plain", "0"), ("compressed", "1")):
    with config.knob_env(CYLON_TPU_SHUFFLE_PACK="1",
                         CYLON_TPU_SHUFFLE_COMPRESS=mode):
        before = metrics.counter_value("shuffle.bytes_sent")
        s = line.shuffle(["l_orderkey"])
        sent = metrics.counter_value("shuffle.bytes_sent") - before
        res[label] = (s.row_count, shards(s), sent)
assert res["plain"][0] == res["compressed"][0]
for (s0, c0, f0), (s1, c1, f1) in zip(res["plain"][1], res["compressed"][1]):
    assert s0 == s1 and c0 == c1
    for b0, b1 in zip(f0, f1):
        for x, y in zip(b0, b1):
            if x is None:
                assert y is None
            else:
                np.testing.assert_array_equal(x, y)
rec = {"rows": int(res["plain"][0]),
       "bytes_plain": int(res["plain"][2]),
       "bytes_compressed": int(res["compressed"][2]),
       "ratio": res["plain"][2] / max(1, res["compressed"][2]),
       "bytes_saved": int(metrics.counter_value("shuffle.bytes_saved"))}
with open(f"{sys.argv[1]}/compress_smoke.json", "w") as fh:
    json.dump(rec, fh, indent=1, sort_keys=True)
PYEOF
rc=$?
if [ $rc -eq 0 ]; then
  python - "$CS" <<'PYEOF'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/compress_smoke.json"))
assert rec["ratio"] > 1.5, rec
assert rec["bytes_saved"] > 0, rec
print(f"compression smoke ok: {rec['bytes_plain']} -> "
      f"{rec['bytes_compressed']} bytes sent ({rec['ratio']:.2f}x) on a "
      f"{rec['rows']}-row low-cardinality Q3 lineitem shuffle, "
      f"bit-identical shards")
PYEOF
  rc=$?
fi
rm -rf "$CS"
if [ $rc -ne 0 ]; then
  echo "compression smoke failed (rc=$rc); fix the payload encoder before the full tree" >&2
  exit $rc
fi
# profiler smoke (ISSUE-12): TPC-H Q10 with the query profiler on — the
# OpenMetrics endpoint is scraped MID-RUN (a thread concurrent with
# plan.execute), the exposition text is validated by the stdlib parser,
# the per-node analyze output must carry nonzero rows/exchange bytes,
# and the statistics catalog must hold the run's observed selectivities
# — asserted from artifact JSON; catches a profiler/exporter regression
# in ~2 min, before the full tree runs
PF=$(mktemp -d /tmp/cylon_profile_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    CYLON_TPU_PROFILE=1 CYLON_TPU_STATS_DIR="$PF/stats" \
    CYLON_TPU_TRACE_DIR="$PF/traces" \
    python - "$PF" <<'PYEOF'
import json, sys, threading, urllib.request
import jax
jax.config.update("jax_platforms", "cpu")
from cylon_tpu.obs import openmetrics
from examples import tpch_q10, tpch_data
from examples.util import default_ctx, table_from_arrays
import numpy as np

out_dir = sys.argv[1]
srv = openmetrics.start_server(0)  # ephemeral scrape port
scrapes = []

def scraper(stop):
    while not stop.wait(0.2):
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5
            ).read().decode()
            scrapes.append(body)
        except OSError:
            pass

ctx = default_ctx(None)
rng = np.random.default_rng(0)
raw_c = tpch_data.customer(0.004, rng)
raw_o = tpch_data.orders(0.004, rng)
raw_l = tpch_data.lineitem(0.004, rng, q5_keys=True,
                           orders_rows=len(raw_o["o_orderkey"]))
raw_l.pop("l_suppkey", None)
plan = tpch_q10.build_plan(
    table_from_arrays(raw_c, ctx), table_from_arrays(raw_o, ctx),
    table_from_arrays(raw_l, ctx),
    table_from_arrays(tpch_data.nation(), ctx))

stop = threading.Event()
th = threading.Thread(target=scraper, args=(stop,), daemon=True)
th.start()
analyzed = plan.explain(analyze=True)   # one profiled execution
_, prof = plan.profile()                # profile artifact + catalog
stop.set(); th.join(timeout=5)
final = urllib.request.urlopen(
    f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read().decode()
scrapes.append(final)
srv.close()

from cylon_tpu.plan import optimizer
stats = optimizer.lookup_stats(plan)
rec = {"analyzed": analyzed, "scrapes": len(scrapes),
       "profile": prof.as_dict(),
       "stats_joins": (stats or {}).get("joins", {}),
       "stats_filters": (stats or {}).get("filters", {}),
       "last_scrape": scrapes[-1]}
with open(f"{out_dir}/profile_smoke.json", "w") as fh:
    json.dump(rec, fh)
# validate EVERY scrape (mid-run included) with the stdlib parser
for body in scrapes:
    openmetrics.parse(body)
PYEOF
rc=$?
if [ $rc -eq 0 ]; then
  python - "$PF" <<'PYEOF'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/profile_smoke.json"))
assert rec["scrapes"] >= 1, rec["scrapes"]
assert "<- [rows" in rec["analyzed"], rec["analyzed"]
nodes = rec["profile"]["nodes"]
assert any(n["rows"] > 0 for n in nodes), nodes
sent = sum(n["metrics"].get("shuffle.bytes_sent", 0) for n in nodes)
assert sent > 0, "no per-node exchange bytes recorded"
assert rec["stats_joins"], "catalog missing join selectivities"
assert rec["stats_filters"], "catalog missing filter selectivities"
assert "cylon_tpu_shuffle_bytes_sent_total" in rec["last_scrape"]
print(f"profiler smoke ok: {len(nodes)} profiled nodes, "
      f"{sent} exchange bytes attributed, {rec['scrapes']} clean "
      f"scrapes, catalog selectivities persisted")
PYEOF
  rc=$?
fi
rm -rf "$PF"
if [ $rc -ne 0 ]; then
  echo "profiler smoke failed (rc=$rc); fix the query profiler before the full tree" >&2
  exit $rc
fi
# adaptive planner smoke (ISSUE-17): a Q10-shaped zipfian-customer-key
# join + NUNIQUE on the world-8 CPU mesh, adaptive off first (profiled,
# seeding the statistics catalog) then adaptive on against the SAME
# catalog — the artifact JSON must record >=1 broadcast join, >=1
# salted key, a >=2x shuffle.bytes_sent drop, and bit-identical results
AD=$(mktemp -d /tmp/cylon_adaptive_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - "$AD" <<'PYEOF'
import json, os, sys
import numpy as np
import pandas as pd
import jax
jax.config.update("jax_platforms", "cpu")
from cylon_tpu import Table, config
from cylon_tpu.context import CylonContext, TPUConfig
from cylon_tpu.obs import metrics

td = sys.argv[1]
ctx = CylonContext.InitDistributed(TPUConfig(world_size=8))
rng = np.random.default_rng(42)
n, nkeys = 1 << 14, 512
# zipfian customer key: the Q10 shape where a few customers dominate
ck = (np.minimum(rng.zipf(1.3, n), nkeys) - 1).astype(np.int32)
orders = {"c_key": ck,
          "o_total": rng.random(n).astype(np.float64),
          "o_clerk": rng.integers(0, 997, n).astype(np.int64)}
nation = {"c_key": np.arange(nkeys, dtype=np.int32),
          "n_name": (np.arange(nkeys) % 25).astype(np.int64)}
ot = Table.from_numpy(list(orders), list(orders.values()), ctx=ctx)
nt = Table.from_numpy(list(nation), list(nation.values()), ctx=ctx)
q = (ot.plan().join(nt, on="c_key", how="inner")
     .groupby(["l_c_key"], {"o_clerk": ["nunique"]}))

def run(adaptive, profile):
    env = dict(CYLON_TPU_PLAN="1", CYLON_TPU_PLAN_ADAPTIVE=adaptive,
               CYLON_TPU_STATS_DIR=os.path.join(td, "stats"),
               CYLON_TPU_PLAN_SKEW_SALT="1.2")
    if profile:
        env["CYLON_TPU_PROFILE"] = "1"
    with config.knob_env(**env):
        before = {k: metrics.counter_value(k) for k in
                  ("shuffle.bytes_sent", "plan.broadcast_joins",
                   "plan.keys_salted")}
        out = q.execute()
        d = {k: metrics.counter_value(k) - v for k, v in before.items()}
        return out, d

base, d0 = run("0", True)   # profiled: seeds the statistics catalog
adap, d1 = run("1", False)  # steers on the catalog it just observed
a = adap.to_pandas().sort_values("l_c_key").reset_index(drop=True)
b = base.to_pandas().sort_values("l_c_key").reset_index(drop=True)
pd.testing.assert_frame_equal(a, b)  # bit-identical, float bits included
rec = {"rows": int(adap.row_count),
       "bytes_adaptive": int(d1["shuffle.bytes_sent"]),
       "bytes_baseline": int(d0["shuffle.bytes_sent"]),
       "ratio": d0["shuffle.bytes_sent"] / max(1, d1["shuffle.bytes_sent"]),
       "plan": {"broadcast_joins": int(d1["plan.broadcast_joins"]),
                "keys_salted": int(d1["plan.keys_salted"])},
       "bit_identical": True}
with open(f"{td}/adaptive_smoke.json", "w") as fh:
    json.dump(rec, fh, indent=1, sort_keys=True)
PYEOF
rc=$?
if [ $rc -eq 0 ]; then
  python - "$AD" <<'PYEOF'
import json, sys
rec = json.load(open(f"{sys.argv[1]}/adaptive_smoke.json"))
assert rec["plan"]["broadcast_joins"] >= 1, rec
assert rec["plan"]["keys_salted"] >= 1, rec
assert rec["ratio"] >= 2.0, rec
assert rec["bit_identical"] is True, rec
print(f"adaptive smoke ok: {rec['plan']['broadcast_joins']} broadcast "
      f"join(s) + {rec['plan']['keys_salted']} salted key(s), "
      f"{rec['bytes_baseline']} -> {rec['bytes_adaptive']} bytes sent "
      f"({rec['ratio']:.1f}x), bit-identical to the PR-9 plan")
PYEOF
  rc=$?
fi
rm -rf "$AD"
if [ $rc -ne 0 ]; then
  echo "adaptive planner smoke failed (rc=$rc); fix the cost-based planner before the full tree" >&2
  exit $rc
fi
# streaming ingestion smoke (ISSUE-19): three appended micro-batches
# with a refresh after each, kill -9 (os._exit at the journal-commit
# fault point) INSIDE the third append, then a fresh process re-runs the
# identical driver — committed appends replay as idempotent no-ops, the
# torn batch lands cleanly, and the final refresh must be bit-identical
# to a journal-free cold recompute while folding ONLY the delta
# (rows_delta == batch rows, plan_cache.miss == 0 on the reused plan);
# asserted from the artifact JSON — catches a streaming-state
# regression in ~30 s, before the full tree runs
ST=$(mktemp -d /tmp/cylon_stream_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    CYLON_TPU_DURABLE_DIR="$ST/journal" \
    CYLON_TPU_FAULT_PLAN='journal_commit@3=killhard' \
    python -m tests.stream_worker "$ST/killed.npz" "$ST/killed.json" \
    --append-only >/dev/null 2>&1
krc=$?
if [ $krc -ne 137 ]; then
  echo "streaming smoke: killhard append exited $krc (expected 137)" >&2
  rm -rf "$ST"; exit 1
fi
env JAX_PLATFORMS=cpu \
    CYLON_TPU_DURABLE_DIR="$ST/journal" \
    python -m tests.stream_worker "$ST/resumed.npz" "$ST/resumed.json" \
  && env JAX_PLATFORMS=cpu \
    CYLON_TPU_DURABLE_DIR= \
    python -m tests.stream_worker "$ST/base.npz" "$ST/base.json" \
  && python - "$ST" <<'PYEOF'
import json, sys
import numpy as np
from tests.stream_worker import ROWS
d = sys.argv[1]
stats = json.load(open(f"{d}/resumed.json"))
assert stats["watermark"] == 3 and stats["batch_rows"] == [ROWS] * 3, stats
assert stats["batches_appended"] == 1, stats  # only the torn batch is new
last = stats["refreshes"][-1]
assert last["mode"] == "incremental", last
assert last["rows_delta"] == ROWS, last      # delta == the one new batch
assert last["partial_rows"] == ROWS, last    # device work bounded by it
assert last["parts_run"] == 1, last
assert last["plan_cache_miss"] == 0, last    # the reused plan recompiles 0
r = np.load(f"{d}/resumed.npz", allow_pickle=True)
b = np.load(f"{d}/base.npz", allow_pickle=True)
assert set(r.files) == set(b.files)
for f in b.files:
    assert r[f].dtype == b[f].dtype, f
    np.testing.assert_array_equal(r[f], b[f], err_msg=f)
print(f"streaming smoke ok: resumed refresh folded {last['rows_delta']} "
      f"delta rows (1 of 3 batches, 0 recompiles), bit-identical to the "
      f"journal-free cold recompute")
PYEOF
rc=$?
rm -rf "$ST"
if [ $rc -ne 0 ]; then
  echo "streaming smoke failed (rc=$rc); fix streaming ingestion before the full tree" >&2
  exit $rc
fi
# self-healing journal chaos smoke (ISSUE-20): the 2-replica fleet with
# PER-REPLICA journal roots at RF=2 and replica 0's scrubber armed; after
# a 12-request flood anti-entropy must converge both roots to the same
# run inventory, then the driver flips bytes in committed spills on BOTH
# roots — replica 0's scrubber repairs its copy from the peer
# (scrub_repaired >= 1, asserted from its metrics artifact) while replica
# 1 (scrubber off) heals lazily through read-repair during replays
# (read_repair >= 1), every serve staying bit-identical with zero
# failures; journal_fsck must then find both roots clean (rc 0), and a
# disaster-wiped root rebuilt by journal_restore must replay a cached run
# whole (passes_skipped == passes, plan_cache.miss == 0)
JS=$(mktemp -d /tmp/cylon_journal_smoke.XXXXXX)
env JAX_PLATFORMS=cpu \
    python - "$JS" <<'PYEOF'
import hashlib, json, os, subprocess, sys, threading, time

sys.path.insert(0, os.getcwd())
os.environ.pop("CYLON_TPU_DURABLE_DIR", None)   # driver oracles stay
os.environ.pop("CYLON_TPU_FAULT_PLAN", None)    # journal-off, fault-free
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from cylon_tpu import config, durable, durable_sync, elastic
from cylon_tpu.exec import chunked_join
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.router import QueryRouter, RouterClient

td = sys.argv[1]
j0, j1 = os.path.join(td, "j0"), os.path.join(td, "j1")
router = QueryRouter(world=3, heartbeat_timeout_s=2.5).start()
addr = f"{router.address[0]}:{router.address[1]}"
base_env = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS",
                         "CYLON_TPU_FAULT_PLAN")}
base_env.update(CYLON_TPU_HEARTBEAT_S="0.1",
                CYLON_TPU_HEARTBEAT_TIMEOUT_S="2.5",
                CYLON_TPU_COORD_RECONNECT_S="0",
                CYLON_TPU_DURABLE_RF="2",
                CYLON_TPU_TRACE_DIR=os.path.join(td, "traces"))
procs = []
for r in range(2):
    env = dict(base_env)
    env["CYLON_TPU_DURABLE_DIR"] = (j0, j1)[r]
    if r == 0:
        # the deterministic split: replica 0 heals by SCRUB, replica 1
        # (no scrubber) only by read-repair during a replayed serve
        env["CYLON_TPU_SCRUB_S"] = "0.5"
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "tests.router_worker", str(r), "3", addr],
        env=env))


def digests(root):
    return {fp: rec["digest"]
            for fp, rec in durable.journal_digests(root).items()}


def first_entry(root, fp):
    m = durable.read_manifest(os.path.join(root, fp))
    return m["passes"][sorted(m["passes"])[0]]


def flip(root, fp):
    """Flip one byte mid-spill; returns (path, manifest sha) to poll."""
    e = first_entry(root, fp)
    path = os.path.join(root, fp, str(e["file"]))
    with open(path, "r+b") as fh:
        fh.seek(os.path.getsize(path) // 2)
        b = fh.read(1)
        fh.seek(-1, 1)
        fh.write(bytes([b[0] ^ 0xFF]))
    return path, e["sha256"]


def sha(path):
    h = hashlib.sha256()
    try:
        with open(path, "rb") as fh:
            for c in iter(lambda: fh.read(1 << 20), b""):
                h.update(c)
    except OSError:
        # mid-heal window: quarantine evicted the run and the
        # anti-entropy re-pull has not landed the file yet
        return None
    return h.hexdigest()


summary = {}
try:
    agent = elastic.Agent(addr, 2, interval_s=0.1, timeout_s=2.5,
                          reconnect_s=0.0).start()
    deadline = time.monotonic() + 120
    while router.router_status()["replicas_live"] < 2:
        assert time.monotonic() < deadline, "replicas never registered"
        time.sleep(0.1)
    cli = RouterClient(addr)

    def mk(seed):
        rg = np.random.default_rng(seed)
        n = 1200
        return ({"k": rg.integers(0, n, n).astype(np.int64),
                 "a": rg.random(n).astype(np.float32)},
                {"k": rg.integers(0, n, n).astype(np.int64),
                 "b": rg.random(n).astype(np.float32)})

    inputs = [mk(300 + i) for i in range(4)]
    oracles = [chunked_join(l, r, on="k", passes=2, mode="hash")[0]
               for l, r in inputs]

    def check(i, res):
        base = oracles[i % 4]
        assert set(res) == set(base), i
        for k in res:
            a, b = np.asarray(res[k]), np.asarray(base[k])
            assert a.dtype == b.dtype, (i, k)
            np.testing.assert_array_equal(a, b, err_msg=f"req {i} col {k}")

    outs, errs, lock = {}, [], threading.Lock()

    def one(i):
        l, r = inputs[i % 4]
        try:
            res, _ = cli.route(f"tenant-{i % 4}", "kjoin", l, r, on="k",
                               passes=2, mode="hash", timeout_s=300)
            with lock:
                outs[i] = res
        except Exception as e:
            with lock:
                errs.append((i, e))

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(12)]
    for t in threads:
        t.start()
        time.sleep(0.05)
    for t in threads:
        t.join(360)
    assert all(not t.is_alive() for t in threads), "a routed request hung"
    assert not errs, errs
    for i, res in outs.items():
        check(i, res)
    summary["served"] = len(outs)
    summary["failures"] = len(errs)

    # anti-entropy convergence: RF=2 must drive BOTH roots to the same
    # run inventory (manifest digests compare equal across roots)
    deadline = time.monotonic() + 90
    while True:
        d0, d1 = digests(j0), digests(j1)
        if len(d0) >= 2 and d0 == d1:
            break
        assert time.monotonic() < deadline, ("anti-entropy never "
                                             "converged", d0, d1)
        time.sleep(0.2)
    summary["replicated_runs"] = len(d0)
    fps = sorted(d0)

    # seeded bitrot, phase 1 (scrub): flip a spill byte in TWO runs on
    # replica 0's root with NO requests in flight — only its background
    # scrubber can heal these, and at most one run is skipped as live,
    # so at least one heals within a couple of 0.5s rounds
    scrub_targets = [flip(j0, fp) for fp in fps[:2]]
    deadline = time.monotonic() + 60
    while not any(sha(p) == want for p, want in scrub_targets):
        assert time.monotonic() < deadline, "scrubber never repaired"
        time.sleep(0.25)

    # seeded bitrot, phase 2 (read-repair): flip a spill byte on replica
    # 1's root, then replay the flood inputs until every damaged file on
    # both roots carries its manifest sha again — replica 1 has no
    # scrubber, so its heal can only come from load-time read-repair,
    # and the replays that hit the damage must still serve bit-identical
    rr_path, rr_sha = flip(j1, fps[-1])
    targets = scrub_targets + [(rr_path, rr_sha)]
    start = time.monotonic()
    deadline = start + 120
    i = 0
    while not all(sha(p) == want for p, want in targets):
        assert time.monotonic() < deadline, (
            "heal stalled", [(p, sha(p) == w) for p, w in targets])
        if time.monotonic() > start + 20:
            # a scrub target can stay corrupt only while it is replica
            # 0's LIVE run (scrub skips under its own writer) and no
            # replay landed on replica 0 to move the pointer; un-flip it
            # (the XOR is its own inverse) — scrub_repaired was already
            # banked on the other target in phase 1
            for p, want in scrub_targets:
                if sha(p) not in (want, None):
                    with open(p, "r+b") as fh:
                        fh.seek(os.path.getsize(p) // 2)
                        b = fh.read(1)
                        fh.seek(-1, 1)
                        fh.write(bytes([b[0] ^ 0xFF]))
        l, r = inputs[i % 4]
        res, _ = cli.route(f"tenant-{i % 4}", "kjoin", l, r, on="k",
                           passes=2, mode="hash", timeout_s=300)
        check(i, res)
        i += 1
        time.sleep(0.2)
    summary["heal_replays"] = i
finally:
    router.stop()
    for p in procs:
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
assert procs[0].returncode == 0, procs[0].returncode
assert procs[1].returncode == 0, procs[1].returncode

# offline integrity check: both roots must come back CLEAN (rc 0)
summary["fsck_rc"] = [
    subprocess.run([sys.executable, "tools/journal_fsck.py", root],
                   capture_output=True).returncode
    for root in (j0, j1)]

# disaster recovery: rebuild an empty root whole from a peer journal,
# then replay a flood run from it — every pass loads from the restored
# journal (passes_skipped == passes) and nothing recompiles
restored = os.path.join(td, "restored")
srv = durable_sync.JournalPeerServer(j1)
try:
    summary["restore"] = durable_sync.journal_restore(
        restored, [srv.address])
finally:
    srv.close()
assert digests(restored) == digests(j1), "restored inventory diverges"
with config.knob_env(CYLON_TPU_DURABLE_DIR=restored):
    obs_metrics.reset()
    res, st = chunked_join(inputs[0][0], inputs[0][1], on="k", passes=2,
                           mode="hash")
check(0, res)
summary["restore_replay"] = {
    "passes": st["passes"],
    "passes_skipped": st.get("passes_skipped", 0),
    "parts_run": st.get("parts_run", 0),
    "plan_cache_miss": int(obs_metrics.counter_value("plan_cache.miss")),
}
with open(f"{td}/summary.json", "w") as fh:
    json.dump(summary, fh, indent=1, sort_keys=True)
print(f"journal chaos smoke: {summary['served']}/12 bit-identical, "
      f"{summary['replicated_runs']} runs replicated, healed after "
      f"{summary['heal_replays']} replays, fsck rc={summary['fsck_rc']}")
PYEOF
rc=$?
if [ $rc -ne 0 ]; then
  echo "journal chaos smoke (run) failed (rc=$rc); fix journal self-healing before the full tree" >&2
  rm -rf "$JS"; exit $rc
fi
python - "$JS" <<'PYEOF'
import glob, json, sys
td = sys.argv[1]
s = json.load(open(f"{td}/summary.json"))
assert s["served"] == 12 and s["failures"] == 0, s
assert s["replicated_runs"] >= 2, s
assert s["fsck_rc"] == [0, 0], s
assert s["restore"]["pulled"] == s["replicated_runs"], s
assert s["restore"]["failed"] == 0, s
rr = s["restore_replay"]
assert rr["passes_skipped"] == rr["passes"] and rr["parts_run"] == 0, rr
assert rr["plan_cache_miss"] == 0, rr


def counters(rank):
    paths = sorted(glob.glob(f"{td}/traces/metrics*.r{rank}.json"))
    assert paths, f"no metrics artifact for rank {rank}"
    return json.load(open(paths[-1]))["counters"]


m0, m1 = counters(0), counters(1)
assert m0.get("durable.scrub_repaired", 0) >= 1, m0
assert m1.get("durable.read_repair", 0) >= 1, m1
assert m0.get("durable.read_repair_failed", 0) == 0, m0
assert m1.get("durable.read_repair_failed", 0) == 0, m1
print(f"journal chaos smoke ok: replica 0 scrub-repaired "
      f"{int(m0['durable.scrub_repaired'])} run(s), replica 1 "
      f"read-repaired {int(m1['durable.read_repair'])} spill(s), both "
      f"roots fsck-clean, restore replayed {rr['passes']} passes with 0 "
      f"plan-cache misses")
PYEOF
rc=$?
rm -rf "$JS"
if [ $rc -ne 0 ]; then
  echo "journal chaos smoke (artifact) failed (rc=$rc); fix journal self-healing before the full tree" >&2
  exit $rc
fi
env JAX_PLATFORMS=cpu \
    CYLON_TEST_NO_COMPILE_CACHE=1 PYTHONFAULTHANDLER=1 \
    timeout 14400 python -m pytest tests/ -q -p no:cacheprovider -x \
    > "$OUT" 2>&1
rc=$?
echo "full-tree cold run rc=$rc; tail:" >&2
tail -5 "$OUT" >&2
exit $rc
