"""The fetch of a sharded table moves every live row to the host once.

Pinned here: ``to_numpy`` / ``to_arrow`` / ``write_csv`` of a table of
several shards give what the same rows give from one shard, whatever the
shards' counts (uneven, empty, full to capacity, off the rounding step) and
whatever the columns hold (nulls, strings with ``lengths``, int64, float64,
bool, a narrow count buffer, a replicated buffer); the bytes that cross are
the live rows plus at most one rounding step a shard and buffer, and
nothing goes back up; a count that moves by less than a step compiles no
new slice program.
"""
import jax
import jax.monitoring
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from cylon_tpu import Table, dtypes
from cylon_tpu import column as column_mod
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.table import (_FETCH_STEPS, _assemble_sharded,
                             _sharded_counts)

CAP = 4 * _FETCH_STEPS  # a step of 4 rows
STEP = CAP // _FETCH_STEPS
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# per-shard live counts, cycled over the mesh
COUNTS = {
    "uneven": [5 * STEP, 40 * STEP, STEP, 100 * STEP],
    "empty_shard": [12 * STEP, 0, 64 * STEP, 0],
    "full_shard": [CAP, 8 * STEP, CAP, 3 * STEP],
    "off_step": [1, STEP + 1, 7 * STEP - 1, CAP - 1],
}


def _counts(kind: str, world: int) -> list:
    return [COUNTS[kind][s % 4] for s in range(world)]


def _rows(n: int, seed: int = 5):
    """{name: (values, validity or None, logical dtype or None)}: every
    kind of column the fetch carries, each with null rows."""
    rng = np.random.default_rng(seed)
    nulls = rng.random(n) < 0.1
    f = rng.random(n)
    f[rng.random(n) < 0.1] = np.nan  # NaN ingests as a null
    s = np.array([None if i % 11 == 3 else f"row-{i % 37}" * (i % 3)
                  for i in range(n)], object)
    return {
        "i64": (rng.integers(-2**40, 2**40, n), ~nulls, None),
        "f64": (f, None, None),
        "flag": (rng.random(n) < 0.5, None, None),
        # a narrow-mode count: 32-bit buffer under a 64-bit logical type
        "count": (rng.integers(0, 1000, n).astype(np.int32), None,
                  dtypes.int64),
        "s": (s, None, None),
    }


def _column(values, validity, dtype, capacity=None):
    return column_mod.from_numpy(values, validity=validity, dtype=dtype,
                                 capacity=capacity)


def _sharded(ctx, rows: dict, counts: list, cap: int = CAP) -> Table:
    """Shard s holds rows [sum(counts[:s]), sum(counts[:s+1])) in buffers
    of ``cap`` rows."""
    offs = np.concatenate([[0], np.cumsum(counts)])
    cols = []
    for values, validity, dtype in rows.values():
        cols.append(_assemble_sharded(
            [_column(values[lo:hi], None if validity is None
                     else validity[lo:hi], dtype, cap)
             for lo, hi in zip(offs[:-1], offs[1:])], ctx))
    return Table(tuple(cols), _sharded_counts(counts, ctx), tuple(rows), ctx)


def _single(ctx, rows: dict, n: int) -> Table:
    return Table.from_columns(
        {name: _column(*spec) for name, spec in rows.items()}, n, ctx=ctx)


def _pair(request, world_fixture: str, kind: str):
    ctx = request.getfixturevalue(world_fixture)
    counts = _counts(kind, ctx.GetWorldSize())
    n = sum(counts)
    rows = _rows(n)
    return (_sharded(ctx, rows, counts),
            _single(request.getfixturevalue("local_ctx"), rows, n), counts)


def _export_numpy(table, tmp_path):
    return {k: (v.dtype, v.tolist()) for k, v in table.to_numpy().items()}


def _export_arrow(table, tmp_path):
    return table.to_arrow()


def _export_csv(table, tmp_path):
    path = tmp_path / f"t{table.num_shards}.csv"
    table.to_csv(str(path))
    return path.read_text()


@pytest.mark.parametrize("export", [_export_numpy, _export_arrow,
                                    _export_csv],
                         ids=["to_numpy", "to_arrow", "write_csv"])
@pytest.mark.parametrize("kind", list(COUNTS))
@pytest.mark.parametrize("world_fixture", ["ctx4", "ctx8"])
def test_sharded_export_equals_the_one_shard_export(world_fixture, kind,
                                                    export, request,
                                                    tmp_path):
    sharded, single, counts = _pair(request, world_fixture, kind)
    got, want = export(sharded, tmp_path), export(single, tmp_path)
    if export is _export_arrow:
        assert got.equals(want) and got.num_rows == sum(counts)
    else:
        assert got == want


@pytest.mark.parametrize("kind", list(COUNTS))
def test_fetch_moves_live_rows_once_and_nothing_up(kind, request):
    sharded, _, counts = _pair(request, "ctx4", kind)
    row_bytes = sum(b.dtype.itemsize * int(np.prod(b.shape[1:]))
                    for b in jax.tree_util.tree_leaves(sharded.columns))
    obs_metrics.reset()
    sharded.to_numpy()
    counters = obs_metrics.snapshot()["counters"]
    moved = counters["table.fetch.bytes"] - sharded.row_counts.nbytes
    live = sum(counts) * row_bytes
    rounded = sum(min(CAP, -(-n // STEP) * STEP) for n in counts) * row_bytes
    assert live <= moved == rounded < live + len(counts) * STEP * row_bytes
    assert "table.fetch.h2d_bytes" not in counters


def test_a_one_shard_export_never_enters_the_sharded_path(local_ctx,
                                                          monkeypatch,
                                                          tmp_path):
    """The one-chip cell's fetch is the parent's: the table's own device
    columns through ``column.to_numpy``, a slice and a copy a buffer."""
    from cylon_tpu import table as table_mod

    def refuse(*a, **kw):
        raise AssertionError("a one-shard table took the sharded fetch")

    monkeypatch.setattr(table_mod, "_live_shard_rows", refuse)
    monkeypatch.setattr(Table, "_fetched_columns", refuse)
    single = _single(local_ctx, _rows(100), 100)
    obs_metrics.reset()
    assert len(single.to_numpy()["i64"]) == single.to_arrow().num_rows == 100
    assert _export_csv(single, tmp_path).count("\n") == 101
    assert "table.fetch.h2d_bytes" not in obs_metrics.snapshot()["counters"]


def test_a_replicated_buffer_is_read_shard_by_shard(ctx4, local_ctx):
    counts = _counts("uneven", 4)
    n = sum(counts)
    rows = _rows(n)
    sharded = _sharded(ctx4, rows, counts)
    everywhere = NamedSharding(ctx4.mesh, P())
    col = sharded.columns[0]
    sharded.columns = (column_mod.Column(
        jax.device_put(col.data, everywhere),
        jax.device_put(col.validity, everywhere), None, col.dtype),
    ) + sharded.columns[1:]
    assert _export_numpy(sharded, None) == _export_numpy(
        _single(local_ctx, rows, n), None)


def test_per_shard_views_hold_each_shards_live_rows(ctx4):
    counts = _counts("empty_shard", 4)
    rows = _rows(sum(counts))
    offs = np.concatenate([[0], np.cumsum(counts)])
    shards = _sharded(ctx4, rows, counts)._addressable_host_shards()
    assert [(sid, cnt) for sid, _, cnt in shards] == list(enumerate(counts))
    for sid, cols, cnt in shards:
        got = column_mod.to_numpy(cols[0], cnt)
        ok = rows["i64"][1][offs[sid]:offs[sid + 1]]
        want = rows["i64"][0][offs[sid]:offs[sid + 1]].astype(object)
        want[~ok] = None
        assert got.tolist() == want.tolist()


def test_a_count_inside_the_same_step_compiles_nothing_new(ctx4):
    """``entry.compiles_in_window`` counts these events: a query whose
    groups come out a few more or fewer than the warm-up's fetches with the
    slice programs the warm-up compiled."""
    compiled = []

    def on(event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            compiled.append(event)

    first = [10 * STEP, 3 * STEP - 1, CAP, 21 * STEP - 2]
    again = [10 * STEP - 1, 2 * STEP + 1, CAP - 1, 20 * STEP + 1]
    other = [11 * STEP, 3 * STEP, CAP, 21 * STEP]
    tables = [_sharded(ctx4, _rows(sum(c)), c) for c in (first, again, other)]
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        tables[0].to_numpy()
        assert compiled  # the slices of the first fetch
        del compiled[:]
        out = tables[1].to_numpy()
        assert not compiled
        assert len(out["i64"]) == sum(again)
        tables[2].to_numpy()
        assert compiled  # a step further is a new slice
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
