"""Resilience layer (cylon_tpu/resilience.py): error classification,
bounded retry, deterministic fault injection, and the recovery paths they
drive through the out-of-core engine and the table-level one-shot ops.

Everything here runs on CPU with injected faults whose messages mirror
real PJRT failure text — no TPU and no real OOM needed.  The correctness
contract for every recovery path: the recovered result equals the
uninjected run's result (canonical row order), and the stats prove the
stream RESUMED at the failure point instead of restarting.
"""
import time

import numpy as np
import pytest

from cylon_tpu import exec as exec_mod
from cylon_tpu import resilience
from cylon_tpu.exec import chunked_groupby, chunked_join
from cylon_tpu.resilience import (FaultPlan, InjectedFault, RetryPolicy,
                                  fault_plan, fault_point, retry_call)
from cylon_tpu.status import Code, CylonError, Status
from cylon_tpu.table import Table


def _sorted_rows(res):
    """Canonical row order: the engine's pass concatenation order changes
    when passes split, the row SET must not."""
    names = sorted(res)
    order = np.lexsort(tuple(res[n] for n in names))
    return {n: np.asarray(res[n])[order] for n in names}


def _assert_frames_equal(a, b):
    assert sorted(a) == sorted(b)
    sa, sb = _sorted_rows(a), _sorted_rows(b)
    for n in sa:
        np.testing.assert_array_equal(sa[n], sb[n], err_msg=n)


def _join_inputs(rng, n=3000, dom=400):
    left = {"k": rng.integers(0, dom, n).astype(np.int32),
            "a": rng.integers(0, 1 << 20, n).astype(np.int64)}
    right = {"k": rng.integers(0, dom, n).astype(np.int32),
             "b": rng.integers(0, 1 << 20, n).astype(np.int64)}
    return left, right


# ---------------------------------------------------------------------------
# Status.from_exception classification
# ---------------------------------------------------------------------------

def test_classify_resource_exhausted():
    e = RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer: "
                     "attempting to allocate 2.50G")
    st = Status.from_exception(e)
    assert st.code == Code.OutOfMemory
    assert "RESOURCE_EXHAUSTED" in st.msg


@pytest.mark.parametrize("msg", [
    "DEADLINE_EXCEEDED: operation timed out",
    "collective operation timed out after 90s",
    "UNAVAILABLE: connection reset by peer",
])
def test_classify_transient(msg):
    assert Status.from_exception(RuntimeError(msg)).code == Code.ExecutionError


def test_classify_python_exception_types():
    assert Status.from_exception(MemoryError()).code == Code.OutOfMemory
    assert Status.from_exception(TimeoutError()).code == Code.ExecutionError
    assert Status.from_exception(
        ConnectionResetError()).code == Code.ExecutionError


def test_classify_unknown_and_cylon_passthrough():
    assert Status.from_exception(
        ValueError("some logic bug")).code == Code.UnknownError
    err = CylonError(Code.KeyError, "no column x")
    st = Status.from_exception(err)
    assert st.code == Code.KeyError and st.msg == "no column x"


def test_classify_text_match_is_runtimeerror_only():
    """PJRT failure text matters only on RuntimeError (XlaRuntimeError's
    base); the same words inside a ValueError are a bug's wording and
    must never earn a retry or a split."""
    assert Status.from_exception(
        ValueError("capacity probe timed out")).code == Code.UnknownError
    assert Status.from_exception(
        KeyError("resource_exhausted")).code == Code.UnknownError
    assert Status.from_exception(
        RuntimeError("operation timed out")).code == Code.ExecutionError


# ---------------------------------------------------------------------------
# RetryPolicy / retry_call
# ---------------------------------------------------------------------------

def test_retry_policy_delays_bounded():
    p = RetryPolicy(max_retries=6, base_s=0.1, max_s=0.5)
    ds = list(p.delays())
    assert ds[0] == pytest.approx(0.1)
    assert ds[1] == pytest.approx(0.2)
    assert max(ds) == pytest.approx(0.5)  # capped, not 0.1 * 2**5


def test_retry_call_heals_transient():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("DEADLINE_EXCEEDED: operation timed out")
        return "ok"

    policy = RetryPolicy(max_retries=2, sleep=lambda s: None)
    out, attempts = retry_call(flaky, policy=policy)
    assert out == "ok" and attempts == 3


def test_retry_call_exhaustion_raises_classified():
    policy = RetryPolicy(max_retries=1, sleep=lambda s: None)

    def dead():
        raise RuntimeError("UNAVAILABLE: connection reset by peer")

    with pytest.raises(CylonError) as ei:
        retry_call(dead, policy=policy, site="probe")
    assert ei.value.code == Code.ExecutionError
    assert "probe" in ei.value.msg and "2 attempts" in ei.value.msg


def test_retry_policy_full_jitter_seeded_deterministic():
    """Full jitter draws each delay uniformly from [0, exp_delay],
    deterministically per (seed, retry_index): same seed replays the
    exact schedule, different seeds (= different ranks) spread — the
    anti-thundering-herd property the coordinator reconnect path needs."""
    p7 = RetryPolicy(max_retries=8, base_s=0.1, max_s=0.5, jitter="full",
                     jitter_seed=7)
    ds = [p7.delay(i) for i in range(8)]
    # bounded by the undithered exponential envelope
    plain = RetryPolicy(max_retries=8, base_s=0.1, max_s=0.5)
    for i, d in enumerate(ds):
        assert 0.0 <= d <= plain.delay(i)
    # deterministic replay under the same seed
    assert ds == [RetryPolicy(max_retries=8, base_s=0.1, max_s=0.5,
                              jitter="full", jitter_seed=7).delay(i)
                  for i in range(8)]
    # distinct seeds give distinct schedules (the herd spreads)
    ds9 = [RetryPolicy(max_retries=8, base_s=0.1, max_s=0.5,
                       jitter="full", jitter_seed=9).delay(i)
           for i in range(8)]
    assert ds != ds9
    # jitter off is the exact historical exponential sequence
    none = RetryPolicy(max_retries=3, base_s=0.1, max_s=0.5)
    assert list(none.delays()) == [pytest.approx(0.1), pytest.approx(0.2),
                                   pytest.approx(0.4)]


def test_retry_call_never_retries_bugs_or_oom():
    policy = RetryPolicy(max_retries=5, sleep=lambda s: None)
    calls = {"n": 0}

    def bug():
        calls["n"] += 1
        raise TypeError("a bug must stay a bug")

    with pytest.raises(TypeError):
        retry_call(bug, policy=policy)
    assert calls["n"] == 1

    def oom():
        calls["n"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    calls["n"] = 0
    with pytest.raises(RuntimeError):
        retry_call(oom, policy=policy)
    assert calls["n"] == 1  # OOM heals by splitting, not by repeating


# ---------------------------------------------------------------------------
# fault plan parsing + fault_point
# ---------------------------------------------------------------------------

def test_fault_plan_parse_forms():
    p = FaultPlan.parse("a; b@3=timeout, c@2+=comm")
    assert [(r.site, r.nth, r.kind, r.persistent) for r in p.rules] == [
        ("a", 1, "oom", False), ("b", 3, "timeout", False),
        ("c", 2, "comm", True)]


@pytest.mark.parametrize("spec", ["x@1=lava", "x@zero", "x@0", "@2=oom",
                                  "seed=pi;x@1=oom", "x@1~q=oom",
                                  "x@1~-2=oom"])
def test_fault_plan_rejects_bad_specs(spec):
    with pytest.raises(CylonError) as ei:
        FaultPlan.parse(spec)
    assert ei.value.code == Code.Invalid


def test_fault_plan_seeded_hit_jitter_is_deterministic():
    """`seed=S` + `@N~J`: the fired hit lands in [N, N+J], resolved at
    parse time purely from (seed, rule position) — one spec string is
    one replayable timeline, and sweeping seeds explores different
    interleavings."""
    spec = "seed=5;a@2~3=comm;b@1=oom"
    p1, p2 = FaultPlan.parse(spec), FaultPlan.parse(spec)
    assert [(r.site, r.nth, r.kind) for r in p1.rules] == \
           [(r.site, r.nth, r.kind) for r in p2.rules]
    (a, b) = p1.rules
    assert 2 <= a.nth <= 5 and b.nth == 1  # unjittered rules untouched
    # some seed in a small sweep picks a different hit (jitter is real)
    nths = {FaultPlan.parse(f"seed={s};a@2~3=comm").rules[0].nth
            for s in range(16)}
    assert len(nths) > 1 and nths <= {2, 3, 4, 5}
    # without a seed entry the jitter still resolves (seed defaults 0)
    assert 2 <= FaultPlan.parse("a@2~3=comm").rules[0].nth <= 5


def test_fault_schedule_composes_and_roundtrips():
    """FaultSchedule chains events (the control-plane kinds included)
    into a CYLON_TPU_FAULT_PLAN spec whose parse resolves to the same
    timeline; install() drives fault_point like any plan."""
    from cylon_tpu import resilience

    sched = (resilience.FaultSchedule(seed=11)
             .at("elastic.coordinator", "coordinator_restart", nth=2)
             .at("elastic.rpc.r1", "coord_partition", nth=1, jitter=2,
                 persistent=True)
             .at("exec.pass", "delay", nth=1))
    spec = sched.spec()
    assert spec.startswith("seed=11;")
    assert "coordinator_restart" in spec and "+=coord_partition" in spec
    got = [(r.site, r.nth, r.kind, r.persistent)
           for r in FaultPlan.parse(spec).rules]
    want = [(r.site, r.nth, r.kind, r.persistent)
            for r in sched.plan().rules]
    assert got == want
    assert got[0] == ("elastic.coordinator", 2, "coordinator_restart",
                      False)
    assert got[1][0] == "elastic.rpc.r1" and 1 <= got[1][1] <= 3 \
        and got[1][3] is True
    # unknown kinds rejected at composition time, not at fire time
    with pytest.raises(CylonError):
        resilience.FaultSchedule().at("x", "lava")
    # install() makes it the active plan: coord_partition surfaces at
    # the agent RPC probe as an InjectedFault the caller converts
    with (resilience.FaultSchedule(seed=1)
          .at("x", "comm", nth=1).install()) as plan:
        with pytest.raises(InjectedFault):
            fault_point("x")
        assert plan.fired == [("x", "comm", 1)]


def test_coord_slow_fault_kind_delays_and_continues(monkeypatch):
    """coord_slow is a delayed reply, never a lost one: the probe sleeps
    CYLON_TPU_FAULT_DELAY_S and returns."""
    from cylon_tpu import config

    with config.knob_env(CYLON_TPU_FAULT_DELAY_S="0.05"):
        with fault_plan("verb@1=coord_slow") as plan:
            t0 = time.monotonic()
            fault_point("verb")  # no raise
            assert time.monotonic() - t0 >= 0.05
            assert plan.fired == [("verb", "coord_slow", 1)]


def test_fault_point_fires_on_nth_hit_only():
    with fault_plan("site@2=oom") as plan:
        fault_point("site")                    # hit 1: no fire
        fault_point("other")                   # other sites untouched
        with pytest.raises(InjectedFault) as ei:
            fault_point("site")                # hit 2: fires
        fault_point("site")                    # hit 3: no fire again
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    assert plan.hits == {"site": 3, "other": 1}
    assert plan.fired == [("site", "oom", 2)]
    fault_point("site")  # no active plan: free no-op


def test_fault_point_env_plan(monkeypatch):
    monkeypatch.setenv("CYLON_TPU_FAULT_PLAN", "envsite@1=timeout")
    with pytest.raises(InjectedFault) as ei:
        fault_point("envsite")
    assert resilience.classify(ei.value) == Code.ExecutionError
    monkeypatch.delenv("CYLON_TPU_FAULT_PLAN")
    fault_point("envsite")  # plan cleared with the env var


# ---------------------------------------------------------------------------
# recovery: the chunked engine (the acceptance-criterion path)
# ---------------------------------------------------------------------------

@pytest.mark.fault
@pytest.mark.parametrize("site", ["pass_dispatch", "host_fetch"])
def test_injected_oom_resumes_stream_at_doubled_passes(rng, site):
    """One OOM mid-stream: the engine keeps the completed pass's frame,
    re-plans only the remaining parts at doubled pass count, and the
    result is byte-identical (canonical row order) to an uninjected run."""
    left, right = _join_inputs(rng)
    base, base_stats = chunked_join(left, right, on="k", passes=4,
                                    mode="hash")
    with fault_plan(f"{site}@2=oom") as plan:
        res, stats = chunked_join(left, right, on="k", passes=4,
                                  mode="hash")
    assert plan.fired == [(site, "oom", 2)]
    assert stats["oom_splits"] == 1
    # pass 0 completed before the fault and was NOT re-run; the 3
    # remaining level-0 parts each split in two: 1 + 3*2 parts executed
    # (a restart at doubled granularity would have run 8)
    assert stats["parts_run"] == 7
    assert stats["passes"] == base_stats["passes"] == 4
    _assert_frames_equal(res, base)


@pytest.mark.fault
def test_persistent_oom_exhausts_splits(rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_MAX_OOM_SPLITS", "2")
    left, right = _join_inputs(rng, n=500)
    with fault_plan("pass_dispatch@1+=oom"):
        with pytest.raises(CylonError) as ei:
            chunked_join(left, right, on="k", passes=2, mode="hash")
    assert ei.value.code == Code.OutOfMemory
    assert "CYLON_TPU_MAX_OOM_SPLITS" in ei.value.msg


@pytest.mark.fault
def test_hot_key_oom_fails_fast(rng, monkeypatch):
    """A failing part whose rows all share one key is a key-domain atom:
    no refinement can shrink it, so the engine must raise on the FIRST
    OOM instead of burning the whole split budget on no-op rebuilds."""
    monkeypatch.setenv("CYLON_TPU_MAX_OOM_SPLITS", "6")
    n = 2000
    left = {"k": np.full(n, 7, np.int32),
            "a": np.arange(n, dtype=np.int64)}
    right = {"k": np.full(n, 7, np.int32),
             "b": np.arange(n, dtype=np.int64)}
    with fault_plan("pass_dispatch@1+=oom") as plan:
        with pytest.raises(CylonError) as ei:
            chunked_join(left, right, on="k", passes=2, mode="hash")
    assert ei.value.code == Code.OutOfMemory
    assert "cannot shrink" in ei.value.msg
    assert len(plan.fired) == 1  # failed fast: no rebuild, no second hit


@pytest.mark.fault
def test_hot_head_part_fails_fast_after_one_split(rng, monkeypatch):
    """A hot-key atom confined to the FAILING part, with normal parts
    queued behind it: the head gets exactly one split (the other parts'
    shrinking output sizing might heal an output-driven OOM), then fails
    fast instead of burning the whole split budget on byte-identical
    rebuilds of the atom."""
    monkeypatch.setenv("CYLON_TPU_MAX_OOM_SPLITS", "6")
    cand = np.arange(4096, dtype=np.int32)
    part = exec_mod._hash_pass_ids([cand], 2)
    hot = cand[part == 0][0]          # a key hashing to part 0, alone
    others = cand[part == 1][:128]    # keys hashing to part 1
    def side(name):
        return {"k": np.concatenate([np.full(1500, hot, np.int32),
                                     np.repeat(others, 4)]),
                name: np.arange(1500 + 4 * len(others), dtype=np.int64)}
    with fault_plan("pass_dispatch@1+=oom") as plan:
        with pytest.raises(CylonError) as ei:
            chunked_join(side("a"), side("b"), on="k", passes=2,
                         mode="hash")
    assert ei.value.code == Code.OutOfMemory
    assert "cannot shrink" in ei.value.msg
    assert len(plan.fired) == 2  # one split allowed, then fail-fast


@pytest.mark.fault
def test_hot_head_atom_detected_across_empty_sibling(monkeypatch):
    """The atom's refinement bit puts it in the SECOND child, so its
    empty first-child sibling completes between the two OOMs.  The watch
    is keyed on the atom's id lineage, so the interleaved success must
    not reset it — a real memory-driven OOM never fires on the empty
    sibling, only on the atom's byte-identical child."""
    monkeypatch.setenv("CYLON_TPU_MAX_OOM_SPLITS", "6")
    cand = np.arange(1 << 14, dtype=np.int32)
    h = exec_mod._hash_u64_cols([cand])
    hot = cand[(h % 2 == 0) & ((h >> np.uint64(1)) % 2 == 1)][0]
    others = cand[h % 2 == 1][:128]
    def side(name):
        return {"k": np.concatenate([np.full(1500, hot, np.int32),
                                     np.repeat(others, 4)]),
                name: np.arange(1500 + 4 * len(others), dtype=np.int64)}
    # hit 1: the atom part at level 0; hit 2: its EMPTY first-child
    # sibling (succeeds); hit 3: the atom's child — must fail fast
    with fault_plan("pass_dispatch@1=oom;pass_dispatch@3=oom") as plan:
        with pytest.raises(CylonError) as ei:
            chunked_join(side("a"), side("b"), on="k", passes=2,
                         mode="hash")
    assert ei.value.code == Code.OutOfMemory
    assert "one key-domain atom" in ei.value.msg
    assert [f[2] for f in plan.fired] == [1, 3]


def test_collective_retry_policy_single_process(local_ctx):
    """One process driving the whole mesh: collectives retry under the
    normal policy.  (The multi-process degradation to no-retry is pure
    process-count gating — exercised here by construction, for real in
    the slow multihost suite.)"""
    pol = local_ctx.collective_retry_policy()
    assert pol.max_retries == local_ctx.retry_policy().max_retries


@pytest.mark.fault
def test_transient_fault_retries_pass_in_place(rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_RETRY_BASE_S", "0")
    left, right = _join_inputs(rng)
    base, _ = chunked_join(left, right, on="k", passes=4, mode="hash")
    with fault_plan("pass_dispatch@2=timeout"):
        res, stats = chunked_join(left, right, on="k", passes=4,
                                  mode="hash")
    assert stats.get("retries", 0) == 1
    assert stats.get("oom_splits", 0) == 0  # no splitting for transients
    assert stats["parts_run"] == 4
    _assert_frames_equal(res, base)


@pytest.mark.fault
def test_persistent_transient_fault_exhausts_retries(rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_RETRY_BASE_S", "0")
    monkeypatch.setenv("CYLON_TPU_RETRY_MAX", "1")
    left, right = _join_inputs(rng, n=500)
    with fault_plan("pass_dispatch@1+=comm"):
        with pytest.raises(CylonError) as ei:
            chunked_join(left, right, on="k", passes=2, mode="hash")
    assert ei.value.code == Code.ExecutionError


@pytest.mark.fault
def test_unknown_fault_propagates_unchanged(rng):
    left, right = _join_inputs(rng, n=500)
    with fault_plan("pass_dispatch@1=unknown"):
        with pytest.raises(InjectedFault):
            chunked_join(left, right, on="k", passes=2, mode="hash")


@pytest.mark.fault
def test_groupby_oom_recovery(rng):
    """Partition keys ARE the group keys, so refinement never splits a
    group across passes; int64 sums make recovery exactly comparable."""
    n = 4000
    data = {"k": rng.integers(0, 300, n).astype(np.int32),
            "v": rng.integers(0, 1 << 20, n).astype(np.int64)}
    base, _ = chunked_groupby(data, "k", {"v": ["sum"]}, passes=4)
    with fault_plan("pass_dispatch@1=oom") as plan:
        res, stats = chunked_groupby(data, "k", {"v": ["sum"]}, passes=4)
    assert plan.fired == [("pass_dispatch", "oom", 1)]
    assert stats["oom_splits"] == 1
    assert stats["parts_run"] == 8  # all 4 parts split before any ran
    _assert_frames_equal(res, base)


# ---------------------------------------------------------------------------
# recovery: one-shot table ops fall back to the chunked engine
# ---------------------------------------------------------------------------

@pytest.mark.fault
def test_oneshot_join_falls_back_to_chunked(local_ctx, rng):
    left, right = _join_inputs(rng, n=1500, dom=200)
    lt = Table.from_numpy(["k", "a"], [left["k"], left["a"]], ctx=local_ctx)
    rt = Table.from_numpy(["k", "b"], [right["k"], right["b"]],
                          ctx=local_ctx)
    base = lt.join(rt, on="k", how="inner")
    with fault_plan("oneshot_join@1=oom") as plan:
        res = lt.join(rt, on="k", how="inner")
    assert plan.fired == [("oneshot_join", "oom", 1)]
    assert res.names == base.names
    _assert_frames_equal(res.to_numpy(), base.to_numpy())


@pytest.mark.fault
def test_oneshot_join_fallback_keeps_custom_prefixes(local_ctx, rng):
    """The fallback must produce the SAME schema the one-shot path would
    have: custom collision prefixes survive the chunked-engine detour."""
    from cylon_tpu.config import JoinConfig

    left, right = _join_inputs(rng, n=400, dom=50)
    lt = Table.from_numpy(["k", "x"], [left["k"], left["a"]], ctx=local_ctx)
    rt = Table.from_numpy(["k", "x"], [right["k"], right["b"]],
                          ctx=local_ctx)
    cfg = JoinConfig.of("inner", "sort", ("k",), ("k",),
                        left_prefix="left.", right_prefix="right.")
    base = lt.join(rt, config=cfg)
    with fault_plan("oneshot_join@1=oom"):
        res = lt.join(rt, config=cfg)
    assert res.names == base.names
    assert "left.x" in res.names and "right.x" in res.names
    _assert_frames_equal(res.to_numpy(), base.to_numpy())


@pytest.mark.fault
def test_oneshot_join_fallback_disabled_by_knob(local_ctx, rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_ONESHOT_FALLBACK", "0")
    left, right = _join_inputs(rng, n=200)
    lt = Table.from_numpy(["k", "a"], [left["k"], left["a"]], ctx=local_ctx)
    rt = Table.from_numpy(["k", "b"], [right["k"], right["b"]],
                          ctx=local_ctx)
    with fault_plan("oneshot_join@1=oom"):
        with pytest.raises(InjectedFault):
            lt.join(rt, on="k", how="inner")


@pytest.mark.fault
def test_oneshot_groupby_falls_back_to_chunked(local_ctx, rng):
    n = 2000
    k = rng.integers(0, 150, n).astype(np.int32)
    v = rng.integers(0, 1 << 20, n).astype(np.int64)
    t = Table.from_numpy(["k", "v"], [k, v], ctx=local_ctx)
    base = t.groupby(["k"], {"v": ["sum"]})
    with fault_plan("oneshot_groupby@1=oom") as plan:
        res = t.groupby(["k"], {"v": ["sum"]})
    assert plan.fired == [("oneshot_groupby", "oom", 1)]
    assert res.names == base.names
    _assert_frames_equal(res.to_numpy(), base.to_numpy())


@pytest.mark.fault
def test_oneshot_pipeline_groupby_never_falls_back(local_ctx, rng):
    """The chunked engine is hash-based: silently substituting it for a
    pipeline (run-length) group-by would merge non-adjacent key runs, so
    pipeline propagates the OOM instead of falling back."""
    k = np.array([1, 1, 2, 1], np.int32)  # runs (1, 2, 1): 3 groups
    v = np.array([10, 20, 30, 40], np.int64)
    t = Table.from_numpy(["k", "v"], [k, v], ctx=local_ctx)
    base = t.groupby(["k"], {"v": ["sum"]}, groupby_type="pipeline")
    assert base.row_count == 3
    with fault_plan("oneshot_groupby@1=oom"):
        with pytest.raises(InjectedFault):
            t.groupby(["k"], {"v": ["sum"]}, groupby_type="pipeline")


# ---------------------------------------------------------------------------
# recovery: distributed shuffle retries the exchange
# ---------------------------------------------------------------------------

@pytest.mark.fault
def test_shuffle_transient_fault_retried(ctx2, rng, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_RETRY_BASE_S", "0")
    n = 1000
    lk = rng.integers(0, 100, n).astype(np.int32)
    la = rng.integers(0, 1 << 20, n).astype(np.int64)
    rk = rng.integers(0, 100, n).astype(np.int32)
    rb = rng.integers(0, 1 << 20, n).astype(np.int64)
    lt = Table.from_numpy(["k", "a"], [lk, la], ctx=ctx2)
    rt = Table.from_numpy(["k", "b"], [rk, rb], ctx=ctx2)
    base = lt.distributed_join(rt, on="k", how="inner")
    with fault_plan("shuffle@1=comm") as plan:
        res = lt.distributed_join(rt, on="k", how="inner")
    assert plan.hits["shuffle"] >= 2  # first attempt failed, retry ran
    assert plan.fired == [("shuffle", "comm", 1)]
    _assert_frames_equal(res.to_numpy(), base.to_numpy())


# ---------------------------------------------------------------------------
# progress hook is non-fatal
# ---------------------------------------------------------------------------

def test_broken_progress_hook_never_kills_the_run(rng):
    left, right = _join_inputs(rng, n=500)
    base, _ = chunked_join(left, right, on="k", passes=2, mode="hash")
    calls = {"n": 0}

    def bad_hook(done, total_passes, rows, secs):
        calls["n"] += 1
        raise RuntimeError("observer bug")

    prev = exec_mod.PASS_PROGRESS_HOOK
    exec_mod.PASS_PROGRESS_HOOK = bad_hook
    try:
        with pytest.warns(RuntimeWarning, match="PASS_PROGRESS_HOOK"):
            res, _ = chunked_join(left, right, on="k", passes=2,
                                  mode="hash")
        assert calls["n"] == 1  # disabled after the first failure
        assert exec_mod.PASS_PROGRESS_HOOK is None
    finally:
        exec_mod.PASS_PROGRESS_HOOK = prev
    _assert_frames_equal(res, base)
