"""cylon_tpu.obs — structured tracing, metrics, and Perfetto export.

Contract pinned here: span nesting/attrs land in the event buffer, the
buffer cap drops (and counts) instead of growing, fully-disabled mode is
an alloc-free no-op, exports round-trip the Chrome-trace schema
(ts/dur/ph/pid/tid), metrics snapshots are deterministic, per-rank file
naming never clobbers across ranks, and the instrumented shuffle's
``shuffle.collective_launches`` equals the PR-3 budget goldens (1 packed
/ 13 per-buffer on the canonical 6-column frame).
"""
import json
import os

import numpy as np
import pytest

from cylon_tpu import config
from cylon_tpu.obs import export as obs_export
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import spans as obs_spans
from cylon_tpu.obs import instant, span


@pytest.fixture()
def clean_obs():
    obs_spans.reset()
    obs_metrics.reset()
    yield
    obs_spans.reset()
    obs_metrics.reset()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_attrs(clean_obs):
    with config.knob_env(CYLON_TPU_TRACE="1"):
        with span("outer", table="t1") as s:
            with span("inner"):
                pass
            s.set(rows=42)
        instant("tick", kind="oom")
    evs = obs_spans.events()
    by_name = {e.name: e for e in evs}
    assert set(by_name) == {"outer", "inner", "tick"}
    outer, inner, tick = by_name["outer"], by_name["inner"], by_name["tick"]
    # children close first, so inner precedes outer in record order
    assert evs.index(inner) < evs.index(outer)
    assert inner.depth == outer.depth + 1
    # the child's interval nests inside the parent's
    assert outer.ts <= inner.ts
    assert inner.ts + inner.dur <= outer.ts + outer.dur
    assert outer.attrs == {"table": "t1", "rows": 42}
    assert tick.ph == "i" and tick.dur == 0 and tick.attrs == {"kind": "oom"}
    # aggregates accumulate alongside the event buffer
    rep = obs_spans.aggregate_report()
    assert rep["outer"][1] == 1 and rep["inner"][1] == 1


def test_buffer_cap_drops_and_counts(clean_obs):
    with config.knob_env(CYLON_TPU_TRACE="1",
                         CYLON_TPU_TRACE_BUFFER_CAP="4"):
        for i in range(10):
            instant(f"e{i}")
    assert len(obs_spans.events()) == 4
    assert obs_spans.dropped() == 6
    # the drop counter rides the exports
    path = obs_export.export_trace(path="/tmp/obs_cap_test.json")
    assert obs_export.load_trace(path)["otherData"]["dropped_events"] == 6


def test_disabled_mode_is_alloc_free_noop(clean_obs):
    with config.knob_env(CYLON_TPU_TRACE="0"):
        s1 = span("x")
        s2 = span("y", attr=1)
        with s1:
            pass
        instant("z")
    # one process-wide singleton: nothing allocated, nothing recorded
    assert s1 is s2
    assert obs_spans.events() == ()
    assert obs_spans.aggregate_report() == {}
    # set() on the null span is a chainable no-op
    assert s1.set(rows=1) is s1


def test_default_mode_aggregates_without_events(clean_obs):
    with config.knob_env(CYLON_TPU_TRACE=None):  # registry default: auto
        with span("agg.only"):
            pass
    assert obs_spans.events() == ()
    total, count = obs_spans.aggregate_report()["agg.only"]
    assert count == 1 and total >= 0


def test_timing_shim_is_the_same_substrate(clean_obs):
    from cylon_tpu.utils import span as shim_span
    from cylon_tpu.utils import timing_report

    assert shim_span is obs_spans.span
    with shim_span("shimmed"):
        pass
    assert timing_report()["shimmed"][1] == 1
    assert obs_spans.aggregate_report()["shimmed"][1] == 1


# ---------------------------------------------------------------------------
# export round trip
# ---------------------------------------------------------------------------

def test_perfetto_schema_roundtrip(clean_obs, tmp_path):
    with config.knob_env(CYLON_TPU_TRACE="1"):
        with span("phase.a", n=3):
            with span("phase.b"):
                pass
        instant("mark")
    p = obs_export.export_trace(path=str(tmp_path / "t.json"))
    doc = obs_export.load_trace(p)  # validates name/ph/ts/pid/tid (+dur on X)
    evs = doc["traceEvents"]
    assert len(evs) == 3
    complete = [e for e in evs if e["ph"] == "X"]
    insts = [e for e in evs if e["ph"] == "i"]
    assert {e["name"] for e in complete} == {"phase.a", "phase.b"}
    assert insts[0]["name"] == "mark" and insts[0]["s"] == "t"
    for e in complete:
        assert e["dur"] >= 0 and isinstance(e["ts"], float)
        assert e["args"]["depth"] in (0, 1)
    a = next(e for e in complete if e["name"] == "phase.a")
    assert a["args"]["n"] == 3
    # a corrupted export must not load silently
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": []}))
    with pytest.raises(ValueError):
        obs_export.load_trace(str(bad))


def test_per_rank_export_naming(clean_obs, tmp_path):
    with config.knob_env(CYLON_TPU_TRACE="1",
                         CYLON_TPU_TRACE_DIR=str(tmp_path)):
        instant("one")
        paths = [obs_export.export_trace(rank=r) for r in range(4)]
        mpaths = [obs_export.export_metrics(rank=r) for r in range(4)]
    assert len(set(paths)) == 4 and len(set(mpaths)) == 4
    for r, p in enumerate(paths):
        assert os.path.basename(p) == f"trace.r{r}.json"
        assert obs_export.load_trace(p)["traceEvents"][0]["pid"] == r
    for r, p in enumerate(mpaths):
        assert os.path.basename(p) == f"metrics.r{r}.json"
        assert obs_export.load_metrics(p)["rank"] == r
    # the default rank on the single-process virtual mesh is 0
    with config.knob_env(CYLON_TPU_TRACE_DIR=str(tmp_path)):
        assert obs_export.export_trace().endswith("trace.r0.json")


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_snapshot_deterministic(clean_obs):
    def record(order):
        obs_metrics.reset()
        for name in order:
            obs_metrics.counter_add(name, 2)
        obs_metrics.gauge_max("g.w", 5)
        obs_metrics.gauge_max("g.w", 3)   # watermark keeps the max
        obs_metrics.hist_observe("h.x", 10)
        obs_metrics.hist_observe("h.x", 3)
        return obs_metrics.snapshot()

    s1 = record(["b.two", "a.one", "c.three"])
    s2 = record(["c.three", "a.one", "b.two"])
    assert s1 == s2
    assert json.dumps(s1, sort_keys=False) == json.dumps(s2, sort_keys=False)
    assert list(s1["counters"]) == ["a.one", "b.two", "c.three"]
    assert s1["gauges"]["g.w"] == 5
    h = s1["histograms"]["h.x"]
    assert h["count"] == 2 and h["sum"] == 13 and h["min"] == 3
    assert h["max"] == 10


def test_hbm_watermark_gauge(clean_obs):
    import jax.numpy as jnp

    x = jnp.zeros((1024,), jnp.float32)  # keep a live array around
    total = obs_metrics.record_hbm_watermark()
    assert total >= x.nbytes
    assert obs_metrics.snapshot()["gauges"]["hbm.live_bytes"] >= x.nbytes


# ---------------------------------------------------------------------------
# the instrumented shuffle: acceptance meter for collective accounting
# ---------------------------------------------------------------------------

def _mixed_table(ctx, n=256):
    from cylon_tpu import Table

    rng = np.random.default_rng(7)
    arrs = {
        "k32": rng.integers(0, 50, n).astype(np.int32),
        "v64": rng.integers(-(2 ** 40), 2 ** 40, n).astype(np.int64),
        "f64": rng.normal(size=n),
        "f32": rng.normal(size=n).astype(np.float32),
        "flag": (rng.integers(0, 2, n) == 1),
        "tag": np.array([f"s{i % 13:06d}" for i in range(n)]),
    }
    return Table.from_numpy(list(arrs), list(arrs.values()), ctx=ctx,
                            capacity=n)


@pytest.mark.parametrize("pack,launches", [("perbuf", 13), ("packed", 1)])
def test_shuffle_collective_launch_metric(ctx4, clean_obs, pack, launches):
    """One exchange's ``shuffle.collective_launches`` equals the PR-3
    budget golden: 1 packed / 13 per-buffer on the 6-column frame."""
    from cylon_tpu.parallel import ops as par_ops

    t = _mixed_table(ctx4)
    with config.knob_env(CYLON_TPU_TRACE="1", CYLON_TPU_SHUFFLE_PACK=pack):
        out = par_ops.shuffle(t, (0,))
        assert out.row_count == t.row_count
    c = obs_metrics.snapshot()["counters"]
    assert c["shuffle.exchanges"] == 1
    assert c["shuffle.collective_launches"] == launches
    assert c["shuffle.counts_gathers"] == 1
    assert c["shuffle.bytes_sent"] > 0
    names = {e.name for e in obs_spans.events()}
    assert {"shuffle.plan", "shuffle.exchange"} <= names


def test_distributed_join_trace_exports_nested_spans(ctx4, clean_obs,
                                                     tmp_path):
    """The acceptance shape: a traced world-4 distributed join exports a
    valid Chrome-trace with partition/pack/collective/unpack children and
    local-kernel spans."""
    t = _mixed_table(ctx4)
    with config.knob_env(CYLON_TPU_TRACE="1",
                         CYLON_TPU_TRACE_DIR=str(tmp_path)):
        j = t.distributed_join(t, on="k32")
        assert j.row_count > 0
        tp, mp = obs_export.export_all(prefix="join")
    doc = obs_export.load_trace(tp)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"table.distributed_join", "shuffle.plan", "shuffle.exchange",
            "join.count", "join.gather"} <= names
    # trace-time children appear when this shapes/knobs combination
    # compiles fresh; at minimum the partition pass traced in this test's
    # own plan build on a cold cache.  Assert on the metrics instead of
    # cache state: two shuffles ran.
    m = obs_export.load_metrics(mp)
    assert m["counters"]["shuffle.exchanges"] == 2
    assert m["counters"]["shuffle.collective_launches"] in (2, 26)


def test_task_shuffle_records_exchange_metrics(ctx4, clean_obs, rng):
    """The task-multiplexed exchange launches the same collectives as the
    key shuffle (budget golden task_shuffle.json) — it must account them
    too, not just parallel.ops._shuffled."""
    from cylon_tpu import Table
    from cylon_tpu.parallel.task import LogicalTaskPlan, task_shuffle

    plan = LogicalTaskPlan({0: 3, 1: 1}, world_size=4)
    tables = [Table.from_pydict(
        {"a": rng.integers(0, 100, 40).astype(np.int64),
         "b": rng.random(40)}, ctx=ctx4) for _ in range(2)]
    with config.knob_env(CYLON_TPU_SHUFFLE_PACK="perbuf"):
        task_shuffle(tables, [0, 1], plan)
    c = obs_metrics.snapshot()["counters"]
    assert c["shuffle.exchanges"] == 1
    # a + b + the int64 __task__ routing column: 3 data + 3 validity
    assert c["shuffle.collective_launches"] == 6
    assert c["shuffle.bytes_sent"] > 0


def test_trace_report_tool(clean_obs, tmp_path, capsys):
    import importlib.util

    with config.knob_env(CYLON_TPU_TRACE="1"):
        with span("work.outer"):
            with span("work.inner"):
                pass
        instant("retry", site="s")
    obs_metrics.counter_add("shuffle.collective_launches", 13)
    tp = obs_export.export_trace(path=str(tmp_path / "trace.r0.json"))
    mp = obs_export.export_metrics(path=str(tmp_path / "metrics.r0.json"))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__), "..",
                                     "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.print_report(tp, mp, top=5)
    out = capsys.readouterr().out
    assert "work.outer" in out and "work.inner" in out
    assert "retry" in out
    assert "collective launches" in out and "13" in out
    # self-time attribution: the parent's self excludes the child's span,
    # and repeat calls on ONE loaded doc agree (no event mutation)
    doc = obs_export.load_trace(tp)
    st = mod.self_times(doc["traceEvents"])
    _, outer_total, outer_self = st["work.outer"]
    _, inner_total, _ = st["work.inner"]
    assert outer_self <= outer_total - inner_total + 1e-6
    assert mod.self_times(doc["traceEvents"]) == st
