"""The program's spans and stage names on the profiler's clock.

Pinned here: an ``obs.span`` is a host event of a ``jax.profiler`` trace
(and none at all with tracing off); every name of ``obs.STAGES`` is in the
lowered text of the program it belongs to; the fetch counts the bytes it
moved; the main path's host syncs are counted and steady; ``obs.root`` is
the sum of the depth-0 spans; the HBM gauges read ``memory_stats()``; and
``tools/trace_report.py --device`` reduces a recorded chip trace to the
stage table kept beside it.
"""
import dataclasses
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cylon_tpu import Table, config, dtypes, obs
from cylon_tpu.config import JoinType
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import spans as obs_spans

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
ROWS = 1 << 10


@pytest.fixture()
def clean_obs():
    obs_spans.reset()
    obs_metrics.reset()
    yield
    obs_spans.reset()
    obs_metrics.reset()


@pytest.fixture(scope="module")
def trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(HERE, "..", "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pair(ctx, rows, seed=3):
    rng = np.random.default_rng(seed)
    return tuple(
        Table.from_numpy(["k", v], [rng.integers(0, rows, rows),
                                    rng.random(rows)], ctx=ctx)
        for v in ("a", "b"))


# ---------------------------------------------------------------------------
# spans as host events of the profiler's trace
# ---------------------------------------------------------------------------

def test_span_is_a_host_event_of_the_profiler_trace(clean_obs, tmp_path,
                                                    trace_report):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("x.y", k=1) as s:
            s.set(rows=5)
            jnp.arange(4).block_until_ready()
        with config.knob_env(CYLON_TPU_TRACE="0"):
            off = obs.span("x.off", k=1)
            with off:
                pass
    assert off is obs_spans._NULL
    from bench.trace_reduce import find_xplane

    path = find_xplane(str(tmp_path))
    host = trace_report.load_xplane(path)["host"]
    assert [e[0] for e in host if e[0].startswith("x.")] == ["x.y"]
    # the attributes travel as the event's statistics
    data = jax.profiler.ProfileData.from_file(path)
    (event,) = [ev for plane in data.planes for line in plane.lines
                for ev in line.events if ev.name == "x.y"]
    assert dict(event.stats) == {"k": 1, "rows": 5}
    assert trace_report.is_program_span("x.y")
    assert not trace_report.is_program_span("PjitFunction(sort)")


def test_obs_root_is_the_sum_of_depth0_spans(clean_obs):
    with obs.span("a.outer"):
        with obs.span("a.inner"):
            pass
    with obs.span("b.outer"):
        pass
    with obs.span("a.outer"):
        pass
    rep = obs_spans.aggregate_report()
    assert rep[obs_spans.ROOT][1] == 3
    assert rep[obs_spans.ROOT][0] == pytest.approx(
        rep["a.outer"][0] + rep["b.outer"][0], rel=1e-12)
    assert rep["a.inner"][0] <= rep["a.outer"][0]


# ---------------------------------------------------------------------------
# stage names inside the programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lowered(ctx4):
    """Lowered text (with locations) of each kind of program, at 2^10
    rows, made on first use."""
    from jax.sharding import PartitionSpec as P

    from cylon_tpu.context import CylonContext
    from cylon_tpu.ops import compact, groupby, join, sort
    from cylon_tpu.parallel import shuffle
    from cylon_tpu.utils import shard_map

    texts = {}

    def text(kind):
        if kind in texts:
            return texts[kind]
        left, right = _pair(CylonContext.Init(), ROWS)
        count = left.row_counts[0]
        if kind == "join":
            low = join.join_gather.lower(
                left.columns, count, right.columns, right.row_counts[0],
                left_on=(0,), right_on=(0,), join_type=JoinType.INNER,
                out_capacity=2 * ROWS)
        elif kind == "groupby":
            low = groupby.hash_groupby.lower(
                left.columns, count, key_idx=(0,),
                aggs=((1, groupby.AggOp.SUM), (1, groupby.AggOp.COUNT)))
        elif kind == "sort":
            low = jax.jit(lambda cols, n: sort.sort_rows(cols, n, (0,))
                          ).lower(left.columns, count)
        elif kind == "compact":
            low = jax.jit(lambda mask, perm, x: (
                compact.compact_indices(mask),
                compact.inverse_permute(perm, x))).lower(
                    left.columns[0].validity,
                    jnp.arange(ROWS, dtype=jnp.int32), left.columns[1].data)
        else:  # the packed exchange, on the 4-device mesh
            sharded = _pair(ctx4, ROWS)[0]

            def body(t):
                targets = jnp.zeros((ROWS // 4,), jnp.int32)
                cols, total = shuffle.shuffle_shard(
                    t.columns, t.row_counts[0], targets, 4, ROWS // 4, ROWS)
                return Table(cols, jnp.reshape(total, (1,)), t.names, ctx4)

            with config.knob_env(CYLON_TPU_SHUFFLE_PACK="packed"):
                low = jax.jit(shard_map(
                    body, mesh=ctx4.mesh, in_specs=P("p"), out_specs=P("p"),
                    check_vma=False)).lower(sharded)
        texts[kind] = low.as_text(debug_info=True)
        return texts[kind]

    return text


_PROGRAM_OF = {"join": "join", "groupby": "groupby", "sort": "sort",
               "compact": "compact", "plane": "exchange"}


@pytest.mark.parametrize("stage", obs.STAGES)
def test_stage_name_is_in_its_program(lowered, stage):
    # a location reads "jit(join_gather)/join.ranges/sort"; inside a
    # shard_map body the stack starts anew: "plane.pack/shift_left"
    text = lowered(_PROGRAM_OF[stage.split(".")[0]])
    assert re.search(rf'[/"]{re.escape(stage)}/', text)


def test_stage_refuses_a_name_outside_the_list():
    assert len(obs.STAGES) <= 16 and len(set(obs.STAGES)) == len(obs.STAGES)
    with pytest.raises(ValueError):
        obs.stage("join.nowhere")


# ---------------------------------------------------------------------------
# the fetch and the host syncs
# ---------------------------------------------------------------------------

def _buffer_bytes(cols, rows=None) -> int:
    return sum(np.asarray(b[:rows]).nbytes for c in cols
               for b in (c.data, c.validity))


@pytest.mark.parametrize("shards", [1, 4])
def test_fetch_counts_the_bytes_it_moved(clean_obs, local_ctx, ctx4, shards):
    table = _pair(local_ctx if shards == 1 else ctx4, 200)[0]
    out = table.to_numpy()
    assert len(out["k"]) == 200
    counters = obs_metrics.snapshot()["counters"]
    # four shards of 50 rows, full to capacity: the live rows, once
    live = _buffer_bytes(table.columns, 200) if shards == 1 else 200 * (
        8 + 1 + 8 + 1)
    assert "table.fetch.h2d_bytes" not in counters
    assert counters["table.fetch.bytes"] == table.row_counts.nbytes + live
    rep = obs_spans.aggregate_report()
    assert rep["table.fetch"][1] == 1
    assert rep["table.fetch.d2h"][0] <= rep["table.fetch"][0]
    assert "host.sync" not in rep  # the fetch's reads are its own


FETCH_PARTS = ("table.fetch.d2h", "table.fetch.assemble",
               "table.fetch.convert")


def _result_table(ctx, rows=200, strings=False):
    """A join cell's result: an int64 key, float32 values, a count kept
    narrow (an int32 buffer under an int64 column, as narrow accumulation
    leaves it); with ``strings`` a string column beside them."""
    rng = np.random.default_rng(5)
    names = ["l_k", "sum_a", "count_a"]
    arrays = [rng.integers(0, rows, rows), rng.random(rows, np.float32),
              rng.integers(1, 9, rows).astype(np.int32)]
    if strings:
        names.append("s")
        arrays.append(np.array([f"row{i}" for i in range(rows)], object))
    table = Table.from_numpy(names, arrays, ctx=ctx)
    cols = list(table.columns)
    cols[2] = dataclasses.replace(cols[2], dtype=dtypes.int64)
    return Table(cols, table.row_counts, table.names, table.ctx)


def _export(table, how):
    out = getattr(table, how)()
    assert (out.num_rows if how == "to_arrow" else len(out["l_k"])) == 200
    return out


@pytest.mark.parametrize("strings", [False, True])
@pytest.mark.parametrize("how", ["to_numpy", "to_arrow"])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_fetch_splits_into_copies_assembly_and_conversion(
        clean_obs, local_ctx, ctx4, shards, how, strings):
    table = _result_table(local_ctx if shards == 1 else ctx4,
                          strings=strings)
    out = _export(table, how)
    if how == "to_numpy":
        assert out["count_a"].dtype == np.int64  # the narrow count widened
    rep = obs_spans.aggregate_report()
    count = {n: c for n, (_, c) in rep.items()}
    buffers = len(jax.tree_util.tree_leaves(table.columns))
    wide = sum(np.dtype(b.dtype).itemsize == 8
               for b in jax.tree_util.tree_leaves(table.columns))
    assert wide == 1  # l_k's data; floats, the count, validity, strings: no
    assert count.get("table.fetch.assemble", 0) == (buffers if shards == 4
                                                    else 0)
    assert count["table.fetch.convert"] == len(table.columns)
    assert count["table.fetch.d2h.wide"] == wide
    parts = sum(rep.get(n, (0.0, 0))[0] for n in FETCH_PARTS)
    assert parts <= rep["table.fetch"][0]
    assert rep["table.fetch.d2h.wide"][0] <= rep["table.fetch.d2h"][0]
    assert "host.sync" not in rep  # the fetch's reads are its own


@pytest.mark.parametrize("shards", [1, 4])
def test_the_fetch_parts_are_disjoint_children_of_the_fetch(
        clean_obs, local_ctx, ctx4, shards):
    table = _result_table(local_ctx if shards == 1 else ctx4, strings=True)
    with config.knob_env(CYLON_TPU_TRACE="1"):
        _export(table, "to_numpy")
        _export(table, "to_arrow")
    events = obs_spans.events()
    span = {n: [(e.ts, e.ts + e.dur) for e in events if e.name == n]
            for n in ("table.fetch", "table.fetch.d2h.wide") + FETCH_PARTS}

    def inside(iv, outers):
        return any(a <= iv[0] and iv[1] <= b for a, b in outers)

    assert len(span["table.fetch"]) == 2
    parts = sorted(iv for n in FETCH_PARTS for iv in span[n])
    assert parts and all(inside(iv, span["table.fetch"]) for iv in parts)
    assert all(a[1] <= b[0] for a, b in zip(parts, parts[1:]))
    assert span["table.fetch.d2h.wide"] and all(
        inside(iv, span["table.fetch.d2h"])
        for iv in span["table.fetch.d2h.wide"])


def test_no_fetch_part_opens_with_tracing_off(clean_obs, ctx4):
    table = _result_table(ctx4, strings=True)
    with config.knob_env(CYLON_TPU_TRACE="0"):
        _export(table, "to_numpy")
        _export(table, "to_arrow")
    assert obs_spans.aggregate_report() == {}


FETCH_READERS = {"entry.fetch_assemble_ms_per_query": "table.fetch.assemble",
                 "entry.fetch_convert_ms_per_query": "table.fetch.convert",
                 "entry.fetch_d2h_wide_ms_per_query": "table.fetch.d2h.wide"}


@pytest.mark.parametrize("name", sorted(FETCH_READERS))
def test_the_fetch_part_readers(name):
    """ms of the part per completed query; a part that never opened is a
    measured 0; a program without ``obs.root`` (the parent of the PR that
    added these spans has it, and reads 0) or a window without a completed
    query gives nothing."""
    from types import SimpleNamespace

    from bench.run import load_reader

    def read(spans, queries):
        return load_reader(name)(SimpleNamespace(
            spans=spans, counters={"queries": queries}))

    spans = {"obs.root": (25.0, 20), "table.fetch": (0.9, 5),
             FETCH_READERS[name]: (0.6, 40)}
    assert read(spans, 5) == pytest.approx(120.0)
    assert read({"obs.root": (25.0, 20), "table.fetch": (0.9, 5)}, 5) == 0
    assert read({k: v for k, v in spans.items() if k != "obs.root"},
                5) is None
    assert read({}, 5) is None
    assert read(spans, 0) is None


@pytest.mark.parametrize("shards", [1, 4])
def test_the_fetch_part_readers_read_a_real_fetch(clean_obs, local_ctx,
                                                  ctx4, shards):
    from types import SimpleNamespace

    from bench.run import load_reader

    table = _result_table(local_ctx if shards == 1 else ctx4)
    with obs.span("bench.like.query"):  # a query: obs.root is its span
        _export(table, "to_numpy")
    run = SimpleNamespace(spans=obs_spans.aggregate_report(),
                          counters={"queries": 1})
    got = {n: load_reader(n)(run) for n in FETCH_READERS}
    assert got["entry.fetch_convert_ms_per_query"] > 0
    assert got["entry.fetch_d2h_wide_ms_per_query"] > 0
    assert (got["entry.fetch_assemble_ms_per_query"] > 0) == (shards == 4)


def test_the_limit_uploads_the_live_rows_it_gathered(clean_obs, ctx4):
    """``_gathered_columns`` of a sharded table (the planner's ``limit``,
    ``DataFrame.<column>``) goes on computing on the device: live rows down
    once, up once, nothing else."""
    rows = 203  # shards of 51, 51, 51, 50 in buffers of 64
    table = Table.from_numpy(["k", "a"], [np.arange(rows),
                                          np.arange(rows) / 7],
                             ctx=ctx4, capacity=4 * 64)
    head = table.plan().limit(5).execute()
    counters = obs_metrics.snapshot()["counters"]
    live = rows * (8 + 1 + 8 + 1)
    assert counters["table.fetch.h2d_bytes"] == live
    assert counters["table.fetch.bytes"] == table.row_counts.nbytes + live
    assert "table.fetch.h2d" in obs_spans.aggregate_report()
    assert head.to_numpy()["k"].tolist() == list(range(5))


def test_the_query_syncs_the_same_number_of_times(clean_obs, local_ctx):
    """bench/drivers/join_gbs: run + fetch at 2^12 rows.  The first query
    sizes the join (``join.count``), the later ones check the cached
    capacity: each reads the device once."""
    from bench.drivers import join_gbs

    rows = 1 << 12
    rng = np.random.default_rng(11)
    data = {side: {"k": rng.integers(0, rows, rows), v: rng.random(rows)}
            for side, v in (("left", "a"), ("right", "b"))}
    state = join_gbs.build(local_ctx, {}, data)
    syncs, fetched = [], []
    for _ in range(3):
        before = obs_metrics.counter_value("host.syncs")
        fetched.append(join_gbs.fetch(join_gbs.run(state, {})))
        syncs.append(obs_metrics.counter_value("host.syncs") - before)
    assert syncs[1] == syncs[2] >= 1
    assert all(len(f["l_k"]) == len(fetched[0]["l_k"]) > 0 for f in fetched)
    assert obs_spans.aggregate_report()["host.sync"][1] == sum(syncs)


def test_hbm_gauges_read_memory_stats_of_the_fullest_device(clean_obs,
                                                             monkeypatch):
    class Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    x = jnp.zeros((256,), jnp.float32)
    # the CPU backend reports none: the live-array sum stands alone
    assert obs_metrics.record_hbm_watermark() >= x.nbytes
    assert "hbm.peak_bytes" not in obs_metrics.snapshot()["gauges"]
    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev({"bytes_in_use": 10, "peak_bytes_in_use": 70}),
        Dev({"bytes_in_use": 30, "peak_bytes_in_use": 50}), Dev(None)])
    assert obs_metrics.record_hbm_watermark() >= x.nbytes
    gauges = obs_metrics.snapshot()["gauges"]
    assert gauges["hbm.bytes_in_use"] == 30 and gauges["hbm.peak_bytes"] == 70
    assert gauges["hbm.live_bytes"] >= x.nbytes


# ---------------------------------------------------------------------------
# tools/trace_report.py --device on a recorded chip trace
# ---------------------------------------------------------------------------

def test_device_report_of_the_recorded_chip_trace(trace_report, capsys):
    with open(os.path.join(FIXTURES, "join_gbs_2p16.expected.json")) as f:
        expected = json.load(f)
    rep = trace_report.device_report(
        os.path.join(FIXTURES, "join_gbs_2p16.xplane.pb"))
    assert rep["chips"] == expected["chips"]
    for key in ("window_s", "busy_s", "idle_s_total"):
        assert rep[key] == pytest.approx(expected[key], rel=1e-9)
    for table in ("stages_s", "idle_s"):
        assert rep[table] == pytest.approx(expected[table], rel=1e-9)
    assert rep["spans"] == {k: [v[0], pytest.approx(v[1], rel=1e-9)]
                            for k, v in expected["spans"].items()}
    # every stage the query runs has device time, and its spans are there
    innermost = {name.rsplit("/", 1)[-1] for name in rep["stages_s"]}
    assert {s for s in obs.STAGES
            if not s.startswith("plane.")} <= innermost
    assert {"table.distributed_join", "join.gather", "host.sync",
            "table.groupby", "table.distributed_sort", "table.sort",
            "table.fetch", "table.fetch.d2h"} <= set(rep["spans"])
    assert trace_report.main(["--device", os.path.join(
        FIXTURES, "join_gbs_2p16.xplane.pb")]) == 0
    out = capsys.readouterr().out
    assert "join.gather_right" in out and "table.fetch.d2h" in out
