"""Multi-tenant query service (cylon_tpu/serve/): admission control,
bounded-queue load shedding, per-tenant budgets, the journal-backed
result cache, cancellation, graceful drain, and journal GC.

The acceptance-criterion shape: overload is never a hang or an
unclassified crash — the flood test drives the queue past its bound and
every request either completes bit-identical to the serial oracle or is
shed with `ResourceExhausted`/`Unavailable` + a retry-after hint, under
hard test timeouts; a repeated query is served from the journal result
cache with zero plan-cache misses and zero device passes.
"""
import threading
import time

import numpy as np
import pytest

from cylon_tpu import config, durable, resilience
from cylon_tpu import serve
from cylon_tpu.exec import chunked_join
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import spans as obs_spans
from cylon_tpu.serve import QueryService, TenantBudget
from cylon_tpu.serve import service as service_mod
from cylon_tpu.status import Code, CylonError

#: hard per-request wait — any miss is a hang, the exact failure mode
#: this subsystem exists to eliminate
WAIT_S = 180.0

SHED_CODES = (Code.ResourceExhausted, Code.Unavailable)


def _inputs(seed, n=1500):
    rng = np.random.default_rng(seed)
    left = {"k": rng.integers(0, n, n).astype(np.int64),
            "a": rng.random(n).astype(np.float32)}
    right = {"k": rng.integers(0, n, n).astype(np.int64),
             "b": rng.random(n).astype(np.float32)}
    return left, right


def _assert_bit_identical(a: dict, b: dict) -> None:
    assert set(a) == set(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert x.dtype == y.dtype, (k, x.dtype, y.dtype)
        np.testing.assert_array_equal(x, y, err_msg=k)


@pytest.fixture()
def svc():
    s = QueryService()
    yield s
    s.close()


# ---------------------------------------------------------------------------
# deterministic admission control (the scheduler is pinned by a blocked
# runner, so queue state — and therefore every shed — is exact)
# ---------------------------------------------------------------------------

@pytest.fixture()
def blocked_join(monkeypatch):
    """Replace the join runner with one that parks the scheduler thread
    until released — admission outcomes become a pure function of the
    submission sequence, no timing."""
    started = threading.Event()
    release = threading.Event()
    orig = service_mod._RUNNERS["join"]

    def runner(*args, **kwargs):
        started.set()
        assert release.wait(WAIT_S), "blocked runner never released"
        return orig(*args, **kwargs)

    monkeypatch.setitem(service_mod._RUNNERS, "join", runner)
    yield started, release
    release.set()


def test_bounded_queue_sheds_resource_exhausted(blocked_join):
    started, release = blocked_join
    left, right = _inputs(0)
    svc = QueryService(queue_cap=2)
    try:
        t0 = svc.submit("a", "join", left, right, on="k", passes=1,
                        mode="hash")
        assert started.wait(WAIT_S)  # scheduler busy; queue now exact
        admitted = [svc.submit("b", "join", left, right, on="k", passes=1,
                               mode="hash"),
                    svc.submit("c", "join", left, right, on="k", passes=1,
                               mode="hash")]
        with pytest.raises(CylonError) as ei:
            svc.submit("d", "join", left, right, on="k", passes=1,
                       mode="hash")
        assert ei.value.code == Code.ResourceExhausted
        assert "queue full" in ei.value.msg
        assert ei.value.retry_after_s is not None
        assert ei.value.retry_after_s > 0
        assert obs_metrics.counter_value("serve.shed") >= 1
        release.set()
        for t in [t0] + admitted:
            t.result(timeout=WAIT_S)
        st = svc.stats()
        assert st["admitted"] == 3 and st["shed"] == 1
        assert st["tenants"]["d"]["shed"] == 1
    finally:
        release.set()
        svc.close()


def test_tenant_share_isolates_a_flooding_tenant(blocked_join):
    """One tenant may hold at most ceil(cap * share) queued slots: the
    flooder sheds while another tenant still admits into the SAME
    queue."""
    started, release = blocked_join
    left, right = _inputs(1)
    with config.knob_env(CYLON_TPU_SERVE_TENANT_SHARE="0.5"):
        svc = QueryService(queue_cap=4)
        try:
            first = svc.submit("flood", "join", left, right, on="k",
                               passes=1, mode="hash")
            assert started.wait(WAIT_S)
            ok = [svc.submit("flood", "join", left, right, on="k",
                             passes=1, mode="hash") for _ in range(2)]
            with pytest.raises(CylonError) as ei:
                svc.submit("flood", "join", left, right, on="k",
                           passes=1, mode="hash")
            assert ei.value.code == Code.ResourceExhausted
            assert "share" in ei.value.msg
            # the OTHER tenant is untouched by the flooder's shed
            other = svc.submit("quiet", "join", left, right, on="k",
                               passes=1, mode="hash")
            release.set()
            for t in [first] + ok + [other]:
                t.result(timeout=WAIT_S)
            assert svc.stats()["tenants"]["quiet"]["shed"] == 0
        finally:
            release.set()
            svc.close()


def test_hbm_budget_sheds_at_admission(svc):
    left, right = _inputs(2)
    svc.set_budget("mem", TenantBudget(hbm_bytes=1))
    with pytest.raises(CylonError) as ei:
        svc.submit("mem", "join", left, right, on="k")
    assert ei.value.code == Code.ResourceExhausted
    assert "HBM admission estimate" in ei.value.msg
    assert ei.value.retry_after_s is not None
    # an unbudgeted tenant admits the identical request
    svc.submit("ok", "join", left, right, on="k", passes=1,
               mode="hash").result(timeout=WAIT_S)


@pytest.mark.fault
def test_tenant_flood_fault_kind_sheds_at_admission(svc):
    left, right = _inputs(3)
    with resilience.fault_plan("serve.admit@1=tenant_flood") as plan:
        with pytest.raises(CylonError) as ei:
            svc.submit("t", "join", left, right, on="k")
    assert plan.fired == [("serve.admit", "tenant_flood", 1)]
    assert ei.value.code == Code.ResourceExhausted
    assert ei.value.retry_after_s is not None
    # the next submission admits normally
    svc.submit("t", "join", left, right, on="k", passes=1,
               mode="hash").result(timeout=WAIT_S)


@pytest.mark.fault
def test_shed_fault_kind_sheds_queued_work_at_dispatch(svc):
    left, right = _inputs(4)
    with resilience.fault_plan("serve.dispatch@1=shed") as plan:
        t = svc.submit("t", "join", left, right, on="k", passes=1,
                       mode="hash")
        with pytest.raises(CylonError) as ei:
            t.result(timeout=WAIT_S)
    assert plan.fired == [("serve.dispatch", "shed", 1)]
    assert ei.value.code == Code.Unavailable
    assert t.state == service_mod.SHED
    # the service keeps serving afterwards
    svc.submit("t", "join", left, right, on="k", passes=1,
               mode="hash").result(timeout=WAIT_S)


# ---------------------------------------------------------------------------
# the flood: N tenants on ctx4, bounded queue, zero hangs, admitted
# results bit-identical to the serial oracle
# ---------------------------------------------------------------------------

def test_flood_on_ctx4_sheds_classified_and_serves_exact(ctx4):
    tenants = ["t0", "t1", "t2"]
    per_tenant = {t: _inputs(10 + i, n=1200) for i, t in
                  enumerate(tenants)}
    oracle = {t: chunked_join(l, r, on="k", passes=2, mode="hash",
                              ctx=ctx4)[0]
              for t, (l, r) in per_tenant.items()}
    svc = QueryService(ctx=ctx4, queue_cap=1)
    admitted, shed = [], []
    try:
        # 4 waves x 3 tenants of instant submissions against a
        # single-slot queue: the scheduler cannot possibly drain
        # microsecond-spaced submissions of device work, so the bound is
        # guaranteed to trip — every reject must carry a classified
        # code + retry-after, every admit must complete exactly
        for _ in range(4):
            for t in tenants:
                l, r = per_tenant[t]
                try:
                    admitted.append(
                        (t, svc.submit(t, "join", l, r, on="k", passes=2,
                                       mode="hash")))
                except CylonError as e:
                    shed.append((t, e))
        for t, ticket in admitted:
            res, stats = ticket.result(timeout=WAIT_S)  # zero hangs
            _assert_bit_identical(res, oracle[t])
    finally:
        svc.close()
    assert len(admitted) + len(shed) == 12
    assert len(shed) > 0, "queue bound never tripped"
    for _, e in shed:
        assert e.code in SHED_CODES, e
        assert e.retry_after_s is None or e.retry_after_s > 0
    st = svc.stats()
    assert st["admitted"] == len(admitted)
    assert st["shed"] == len(shed)
    assert st["completed"] == len(admitted)
    assert st["failed"] == 0


# ---------------------------------------------------------------------------
# the journal as a result cache
# ---------------------------------------------------------------------------

def test_repeated_fingerprint_serves_from_cache_zero_compiles(tmp_path):
    left, right = _inputs(20)
    base, _ = chunked_join(left, right, on="k", passes=3, mode="hash")
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path),
                         CYLON_TPU_TRACE="1"):
        with QueryService() as svc:
            t1 = svc.submit("alice", "join", left, right, on="k",
                            passes=3, mode="hash")
            r1, s1 = t1.result(timeout=WAIT_S)
            assert t1.cache_hit is False
            obs_spans.reset()
            obs_metrics.reset()
            t2 = svc.submit("alice", "join", left, right, on="k",
                            passes=3, mode="hash")
            r2, s2 = t2.result(timeout=WAIT_S)
    try:
        # the acceptance meter: zero plan-cache misses, zero compiled or
        # executed passes — the device was never touched
        assert t2.cache_hit is True
        assert obs_metrics.counter_value("serve.cache_hit") == 1
        assert obs_metrics.counter_value("plan_cache.miss") == 0
        assert obs_metrics.counter_value("exec.parts_run") == 0
        assert s2["passes_skipped"] == s2["passes"]
        assert "parts_run" not in s2
        _assert_bit_identical(r1, base)
        _assert_bit_identical(r2, base)
        # per-tenant span attribution rides the event buffer
        reqs = [e for e in obs_spans.events() if e.name == "serve.request"]
        assert [e.attrs["tenant"] for e in reqs] == ["alice"]
        hits = [e for e in obs_spans.events() if e.name == "serve.cache_hit"]
        assert len(hits) == 1 and hits[0].attrs["tenant"] == "alice"
    finally:
        obs_spans.reset()
        obs_metrics.reset()


@pytest.mark.fault
def test_cache_evict_race_reexecutes_instead_of_torn_serve(tmp_path):
    """A GC eviction racing a reader (spills deleted under a replayed
    manifest — the `cache_evict_race` fault kind) must degrade to
    re-execution, never serve a torn journal."""
    left, right = _inputs(21)
    base, _ = chunked_join(left, right, on="k", passes=3, mode="hash")
    obs_metrics.reset()
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path)):
        with QueryService() as svc:
            svc.submit("t", "join", left, right, on="k", passes=3,
                       mode="hash").result(timeout=WAIT_S)
            with resilience.fault_plan(
                    "serve.dispatch@1=cache_evict_race") as plan:
                t2 = svc.submit("t", "join", left, right, on="k",
                                passes=3, mode="hash")
                r2, s2 = t2.result(timeout=WAIT_S)
    assert plan.fired == [("serve.dispatch", "cache_evict_race", 1)]
    assert t2.cache_hit is False
    assert s2["passes_skipped"] == 0
    assert s2["parts_run"] == s2["passes"]
    assert obs_metrics.counter_value("durable.spills_rejected") \
        == s2["passes"]
    _assert_bit_identical(r2, base)
    obs_metrics.reset()


_GC_LOOP_SRC = """\
import sys, time
from cylon_tpu import durable
end = time.time() + float(sys.argv[2])
n = 0
while time.time() < end:
    ev, fr = durable.gc_journal(sys.argv[1], cap=1)
    n += ev
print("evictions", n)
"""


def test_cache_evict_race_with_cross_process_gc(tmp_path):
    """The PR-7 evict-race shape against a REAL second process: a
    replica keeps replaying a journaled fingerprint while another
    process's GC loop (cap=1: evict everything it may) collects the
    shared root under the advisory lease.  Every replay must come back
    bit-identical — a cache hit, or a re-execution of whatever the
    collector tore out from under it — and the lock file must not
    leak."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    left, right = _inputs(26)
    base, _ = chunked_join(left, right, on="k", passes=3, mode="hash")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("CYLON_TPU_DURABLE_DIR", None)
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path)):
        with QueryService() as svc:
            svc.submit("t", "join", left, right, on="k", passes=3,
                       mode="hash").result(timeout=WAIT_S)
            proc = subprocess.Popen(
                [sys.executable, "-c", _GC_LOOP_SRC, str(tmp_path), "4"],
                cwd=repo, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
            try:
                deadline = time.monotonic() + WAIT_S
                while time.monotonic() < deadline:
                    r2, _ = svc.submit(
                        "t", "join", left, right, on="k", passes=3,
                        mode="hash").result(timeout=WAIT_S)
                    _assert_bit_identical(r2, base)
                    if proc.poll() is not None:
                        break
            finally:
                out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err
    assert "evictions" in out
    assert not os.path.exists(os.path.join(str(tmp_path), "GC_LOCK"))


# ---------------------------------------------------------------------------
# per-tenant budgets: deadline + quarantine
# ---------------------------------------------------------------------------

def test_request_deadline_classifies_timeout():
    left, right = _inputs(22, n=4000)
    with config.knob_env(CYLON_TPU_RETRY_MAX="0",
                         CYLON_TPU_RETRY_BASE_S="0"):
        with QueryService(budgets={"slow": TenantBudget(
                deadline_s=0.02)}) as svc:
            t = svc.submit("slow", "join", left, right, on="k", passes=4,
                           mode="hash")
            with pytest.raises(CylonError) as ei:
                t.result(timeout=WAIT_S)
            assert ei.value.code == Code.Timeout
            assert "budget" in ei.value.msg
            assert t.state == service_mod.FAILED
            # an unbudgeted tenant runs the same query to completion
            svc.submit("fast", "join", left, right, on="k", passes=4,
                       mode="hash").result(timeout=WAIT_S)


def test_request_deadline_never_truncates_via_engine_quarantine():
    """A request-budget overrun must FAIL classified Timeout — the guard
    raise bypasses the engine's retry/quarantine machinery entirely, so
    even with CYLON_TPU_QUARANTINE_AFTER=1 armed no healthy part is
    quarantined out and no silently-truncated result is served."""
    left, right = _inputs(29, n=4000)
    q0 = obs_metrics.counter_value("quarantine.parts")
    with config.knob_env(CYLON_TPU_QUARANTINE_AFTER="1",
                         CYLON_TPU_RETRY_BASE_S="0"):
        with QueryService(budgets={"slow": TenantBudget(
                deadline_s=0.02)}) as svc:
            t = svc.submit("slow", "join", left, right, on="k", passes=4,
                           mode="hash")
            with pytest.raises(CylonError) as ei:
                t.result(timeout=WAIT_S)
    assert ei.value.code == Code.Timeout
    assert t.state == service_mod.FAILED
    assert obs_metrics.counter_value("quarantine.parts") == q0


@pytest.mark.fault
def test_poison_tenant_quarantined_others_served(svc):
    left, right = _inputs(23)
    with config.knob_env(CYLON_TPU_SERVE_QUARANTINE_AFTER="2",
                         CYLON_TPU_SERVE_QUARANTINE_S="600",
                         CYLON_TPU_RETRY_MAX="0",
                         CYLON_TPU_RETRY_BASE_S="0"):
        with resilience.fault_plan("pass_dispatch@1+=unknown"):
            for _ in range(2):
                t = svc.submit("poison", "join", left, right, on="k",
                               passes=1, mode="hash")
                with pytest.raises(CylonError):
                    t.result(timeout=WAIT_S)
        # streak reached the threshold: the tenant is quarantined and
        # sheds with Unavailable + the cooldown as retry-after
        with pytest.raises(CylonError) as ei:
            svc.submit("poison", "join", left, right, on="k")
        assert ei.value.code == Code.Unavailable
        assert "quarantined" in ei.value.msg
        assert ei.value.retry_after_s is not None
        assert 0 < ei.value.retry_after_s <= 600
        assert obs_metrics.counter_value("serve.tenants_quarantined") >= 1
        # one poison tenant cannot starve the rest
        r, _ = svc.submit("healthy", "join", left, right, on="k",
                          passes=1, mode="hash").result(timeout=WAIT_S)
        base, _ = chunked_join(left, right, on="k", passes=1, mode="hash")
        _assert_bit_identical(r, base)
        assert svc.stats()["tenants"]["poison"]["quarantined"] is True


def test_quarantine_expires_and_streak_resets(svc):
    left, right = _inputs(24)

    def fail_once():
        with resilience.fault_plan("pass_dispatch@1=unknown"):
            t = svc.submit("t", "join", left, right, on="k", passes=1,
                           mode="hash")
            with pytest.raises(CylonError):
                t.result(timeout=WAIT_S)

    with config.knob_env(CYLON_TPU_SERVE_QUARANTINE_AFTER="2",
                         CYLON_TPU_SERVE_QUARANTINE_S="1.0",
                         CYLON_TPU_RETRY_MAX="0",
                         CYLON_TPU_RETRY_BASE_S="0"):
        fail_once()
        fail_once()
        # the cooldown has to outlast a waiter's wake-up on a loaded host
        # (six xdist workers): at 0.05 s it had run out before this submit
        with pytest.raises(CylonError) as ei:
            svc.submit("t", "join", left, right, on="k")
        assert ei.value.code == Code.Unavailable
        time.sleep(1.1)
        # cooldown elapsed: the tenant re-enters with a CLEAN streak —
        # one post-cooldown failure must NOT re-quarantine (threshold 2)
        fail_once()
        svc.submit("t", "join", left, right, on="k", passes=1,
                   mode="hash").result(timeout=WAIT_S)
        assert svc.stats()["tenants"]["t"]["quarantined"] is False


# ---------------------------------------------------------------------------
# cancellation + graceful drain
# ---------------------------------------------------------------------------

def test_cancel_queued_request(blocked_join):
    started, release = blocked_join
    left, right = _inputs(25)
    svc = QueryService(queue_cap=4)
    try:
        first = svc.submit("a", "join", left, right, on="k", passes=1,
                           mode="hash")
        assert started.wait(WAIT_S)
        queued = svc.submit("a", "join", left, right, on="k", passes=1,
                            mode="hash")
        assert queued.cancel() is True
        with pytest.raises(CylonError) as ei:
            queued.result(timeout=WAIT_S)
        assert ei.value.code == Code.Cancelled
        assert queued.state == service_mod.CANCELLED
        release.set()
        first.result(timeout=WAIT_S)
        assert svc.stats()["cancelled"] == 1
    finally:
        release.set()
        svc.close()


def test_cancel_running_request_stops_at_pass_boundary():
    # a fresh shape forces a compile, so the cancel lands long before
    # the stream finishes; the guard stops it at the next pass boundary
    left, right = _inputs(26, n=3000)
    with QueryService() as svc:
        t = svc.submit("c", "join", left, right, on="k", passes=6,
                       mode="hash")
        time.sleep(0.05)
        t.cancel()
        with pytest.raises(CylonError) as ei:
            t.result(timeout=WAIT_S)
        assert ei.value.code == Code.Cancelled
        assert t.state == service_mod.CANCELLED


def test_drain_sheds_queued_finishes_inflight(blocked_join):
    started, release = blocked_join
    left, right = _inputs(27)
    svc = QueryService(queue_cap=4)
    try:
        running = svc.submit("a", "join", left, right, on="k", passes=1,
                             mode="hash")
        assert started.wait(WAIT_S)
        queued = [svc.submit("b", "join", left, right, on="k", passes=1,
                             mode="hash") for _ in range(2)]

        def release_later():
            time.sleep(0.2)
            release.set()
        threading.Thread(target=release_later, daemon=True).start()
        shed = svc.drain(timeout=WAIT_S)
        # queued work shed with a classified status; in-flight finished
        assert set(shed) == set(queued)
        for q in queued:
            with pytest.raises(CylonError) as ei:
                q.result(timeout=WAIT_S)
            assert ei.value.code == Code.Unavailable
            assert "draining" in ei.value.msg
            assert q.state == service_mod.SHED
        running.result(timeout=WAIT_S)
        assert running.state == service_mod.DONE
        # post-drain submissions shed immediately
        with pytest.raises(CylonError) as ei:
            svc.submit("a", "join", left, right, on="k")
        assert ei.value.code == Code.Unavailable
    finally:
        release.set()
        svc.close()


def test_every_op_kind_serves(svc):
    left, right = _inputs(28)
    data = {"g": left["k"] % 7, "v": left["a"]}
    r, _ = svc.submit("t", "join", left, right, on="k", passes=2,
                      mode="hash").result(timeout=WAIT_S)
    assert len(r["l_k"]) > 0
    r, _ = svc.submit("t", "join_groupby", left, right, on="k",
                      group_by="l_k", agg={"a": ["sum"]}, passes=2,
                      mode="hash").result(timeout=WAIT_S)
    assert len(r["l_k"]) > 0
    r, _ = svc.submit("t", "groupby", data, "g",
                      {"v": ["sum"]}, passes=2).result(timeout=WAIT_S)
    assert len(r["g"]) == 7
    r, _ = svc.submit("t", "sort", data, "v",
                      passes=2).result(timeout=WAIT_S)
    assert np.all(np.diff(r["v"]) >= 0)
    with pytest.raises(CylonError) as ei:
        svc.submit("t", "fuse", data)
    assert ei.value.code == Code.Invalid


# ---------------------------------------------------------------------------
# durable-journal GC: size cap + LRU + manifest-last eviction
# ---------------------------------------------------------------------------

def _journal_three_runs(tmp_path, seed0=30):
    """Three complete journaled runs with distinct fingerprints; returns
    their (left, right) inputs in creation order."""
    inputs = []
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path)):
        for i in range(3):
            l, r = _inputs(seed0 + i)
            chunked_join(l, r, on="k", passes=2, mode="hash")
            inputs.append((l, r))
    return inputs


def test_journal_gc_lru_eviction_respects_access_order(tmp_path):
    inputs = _journal_three_runs(tmp_path)
    runs = serve.contents(str(tmp_path))
    assert len(runs) == 3 and all(r["complete"] for r in runs)
    fps = [r["fingerprint"] for r in runs]  # LRU first = creation order
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path)):
        # touch run 0 (a cache serve freshens its LRU clock)
        l0, r0 = inputs[0]
        time.sleep(0.02)
        _, s = chunked_join(l0, r0, on="k", passes=2, mode="hash")
        assert s["passes_skipped"] == s["passes"]
        total = serve.cache_bytes(str(tmp_path))
        biggest = max(r["bytes"] for r in runs)
        with config.knob_env(
                CYLON_TPU_DURABLE_CAP_BYTES=str(total - biggest + 1)):
            evicted, freed = serve.maybe_gc(str(tmp_path))
    assert evicted >= 1 and freed > 0
    left = {r["fingerprint"] for r in serve.contents(str(tmp_path))}
    # run 1 (now least-recently-used) went first; the touched run 0
    # survived despite being created first
    assert fps[1] not in left
    assert fps[0] in left
    assert obs_metrics.counter_value("durable.gc_runs_evicted") >= 1
    obs_metrics.reset()


def test_journal_gc_cap_unset_is_noop(tmp_path):
    _journal_three_runs(tmp_path, seed0=40)
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path),
                         CYLON_TPU_DURABLE_CAP_BYTES=None):
        assert serve.maybe_gc(str(tmp_path)) == (0, 0)
    assert len(serve.contents(str(tmp_path))) == 3


def test_half_evicted_run_reexecutes_not_torn(tmp_path):
    """The manifest-last eviction order means a crash mid-eviction
    leaves a manifest whose spills are gone: every affected pass must
    re-execute — the output stays exact, nothing is served torn."""
    left, right = _inputs(50)
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path)):
        base, s1 = chunked_join(left, right, on="k", passes=3, mode="hash")
        run = serve.contents(str(tmp_path))[0]
        # simulate the eviction crash window: spills removed, manifest
        # (deleted LAST) still present
        import os
        for fn in os.listdir(run["dir"]):
            if fn != durable.MANIFEST:
                os.remove(os.path.join(run["dir"], fn))
        res, s2 = chunked_join(left, right, on="k", passes=3, mode="hash")
    assert s2["passes_skipped"] == 0
    assert s2["parts_run"] == s2["passes"]
    _assert_bit_identical(res, base)


def test_gc_runs_after_service_requests(tmp_path):
    """A journaled run completing under the service triggers the cap GC
    (the engine runs it when it records the run done), so a long-lived
    server stays under CYLON_TPU_DURABLE_CAP_BYTES without an external
    sweeper."""
    l0, r0 = _inputs(60)
    l1, r1 = _inputs(61)
    with config.knob_env(CYLON_TPU_DURABLE_DIR=str(tmp_path)):
        chunked_join(l0, r0, on="k", passes=2, mode="hash")
        one = serve.cache_bytes(str(tmp_path))
        with config.knob_env(CYLON_TPU_DURABLE_CAP_BYTES=str(one + 1)):
            with QueryService() as svc:
                svc.submit("t", "join", l1, r1, on="k", passes=2,
                           mode="hash").result(timeout=WAIT_S)
                time.sleep(0.05)
        runs = serve.contents(str(tmp_path))
    # the older run was evicted to make room; the fresh one remains
    assert len(runs) == 1
    assert obs_metrics.counter_value("durable.gc_runs_evicted") >= 1
    obs_metrics.reset()


# ---------------------------------------------------------------------------
# per-tenant SLO latency histograms (PR 8: queue-wait vs run split)
# ---------------------------------------------------------------------------

def test_per_tenant_slo_latency_histograms():
    """Every dispatched request records its queue wait (admission to
    dispatch) and run time into per-tenant histograms — the rows the
    fleet status endpoint aggregates and trace_report renders.  Counts
    are deterministic; the snapshot serializes identically across
    recording orders (the obs.metrics contract)."""
    obs_metrics.reset()
    left, right = _inputs(70, n=600)
    with QueryService() as svc:
        for _ in range(2):
            svc.submit("slo-a", "join", left, right, on="k", passes=1,
                       mode="hash").result(timeout=WAIT_S)
        tb = svc.submit("slo-b", "join", left, right, on="k", passes=1,
                        mode="hash")
        tb.result(timeout=WAIT_S)
        tel = svc.telemetry()
    h = obs_metrics.snapshot()["histograms"]
    qa, ra = h["serve.queue_wait_ms[slo-a]"], h["serve.run_ms[slo-a]"]
    assert qa["count"] == 2 and ra["count"] == 2
    assert h["serve.queue_wait_ms[slo-b]"]["count"] == 1
    assert h["serve.run_ms[slo-b]"]["count"] == 1
    assert qa["min"] >= 0 and ra["min"] > 0
    assert ra["sum"] >= ra["max"] >= ra["min"]
    # the ticket carries the same split
    assert tb.queue_wait_s is not None and tb.queue_wait_s >= 0
    assert tb.duration_s is not None and tb.duration_s > 0
    # telemetry: the exact rows the coordinator status verb aggregates
    assert tel["queue_depth"] == 0
    a = tel["tenants"]["slo-a"]
    assert a["served"] == 2 and a["queue_wait_ms"]["count"] == 2
    assert a["run_ms"]["count"] == 2
    assert tel["tenants"]["slo-b"]["served"] == 1
    # telemetry is scoped to the SERVICE, not the process-global metrics
    # registry: a second service must not report the first one's tenants
    with QueryService() as svc2:
        assert svc2.telemetry()["tenants"] == {}
    obs_metrics.reset()


def test_slo_histograms_record_failures_too(monkeypatch):
    """The run histogram describes the service's latency, not just its
    successes: a failing request still lands a run_ms observation (its
    time on the mesh was real), and queue-wait is recorded at
    dispatch."""
    obs_metrics.reset()

    def boom(*args, **kwargs):
        raise RuntimeError("UNAVAILABLE: injected runner failure")

    monkeypatch.setitem(service_mod._RUNNERS, "join", boom)
    left, right = _inputs(71, n=200)
    with config.knob_env(CYLON_TPU_SERVE_QUARANTINE_AFTER="0"):
        with QueryService() as svc:
            t = svc.submit("slo-f", "join", left, right, on="k", passes=1,
                           mode="hash")
            with pytest.raises(CylonError):
                t.result(timeout=WAIT_S)
    h = obs_metrics.snapshot()["histograms"]
    assert h["serve.queue_wait_ms[slo-f]"]["count"] == 1
    assert h["serve.run_ms[slo-f]"]["count"] == 1
    obs_metrics.reset()
