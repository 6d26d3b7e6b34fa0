"""The ragged exchange in rounds, executed.

XLA:CPU has no RaggedAllToAll, so the ragged body is otherwise only traced
and compiled (tests/test_shuffle_pack.py, tests/test_tpu_compile.py).  Here
a fixture puts an all-gather-and-slice equivalent of the collective in its
place -- test code only: the program has no such path -- and shrinks the
operand limit, so that shards of a few hundred rows go in rounds of 32.
The rounded exchange has to equal the whole one and a NumPy repartition,
and the join -> group-by -> sort pipeline through it has to equal pandas.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import PartitionSpec as P

from cylon_tpu import Table, column as colmod
from cylon_tpu.context import PARTITION_AXIS, ctx_cache
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.ops import realization
from cylon_tpu.parallel import collectives, ops as par_ops, plane
from cylon_tpu.parallel import shuffle as shuffle_mod
from cylon_tpu.utils import shard_map

WORLD = 4
SHARD = 256
#: a limit of 64 rows: a round's operand and receive buffer hold 32, eight
#: for each of four destinations
SMALL_LIMIT = 64 * shuffle_mod.RAGGED_ROW_BYTES


def _ragged_all_to_all_by_all_gather(operand, output, input_offsets,
                                     send_sizes, output_offsets, recv_sizes):
    """``lax.ragged_all_to_all`` as documented, from all_gathers: rows
    [input_offsets[t], + send_sizes[t]) of source s's operand are written
    on rank t at s's output_offsets[t]; the rest of ``output`` stays.  A
    receiver whose ``recv_sizes`` disagree with what is sent gets nothing."""
    def everyone(x):
        return jax.lax.all_gather(x, PARTITION_AXIS)

    operands, in_off, sizes, out_off = map(
        everyone, (operand, input_offsets, send_sizes, output_offsets))
    me = jax.lax.axis_index(PARTITION_AXIS)
    agreed = jnp.all(recv_sizes == sizes[:, me])
    rows = jnp.arange(output.shape[0], dtype=jnp.int32)
    for s in range(operands.shape[0]):
        lo, n = out_off[s, me], sizes[s, me]
        inside = agreed & (rows >= lo) & (rows < lo + n)
        src = jnp.clip(in_off[s, me] + rows - lo, 0, operand.shape[0] - 1)
        output = jnp.where(
            inside.reshape((-1,) + (1,) * (output.ndim - 1)),
            jnp.take(operands[s], src, axis=0), output)
    return output


@pytest.fixture()
def ragged_on_cpu(monkeypatch, ctx4):
    """The ragged family on the 4-device CPU mesh, through the stand-in."""
    monkeypatch.setattr(collectives, "ragged_all_to_all",
                        _ragged_all_to_all_by_all_gather)
    monkeypatch.setattr(par_ops, "_probe_ragged", lambda ctx: True)
    probe = ctx_cache(ctx4, "_ragged_probe")
    saved = dict(probe)
    probe.clear()
    yield ctx4
    probe.clear()
    probe.update(saved)


@pytest.fixture()
def small_limit(monkeypatch):
    monkeypatch.setattr(shuffle_mod, "_RAGGED_OPERAND_LIMIT", SMALL_LIMIT)


def _targets(kind: str, rng) -> tuple:
    """(targets[WORLD * SHARD], live rows per shard) of one case."""
    live = np.full(WORLD, SHARD - 9)
    if kind == "uniform":
        tgt = rng.integers(0, WORLD, WORLD * SHARD)
    elif kind == "hot":      # every source sends nine tenths to shard 2
        tgt = np.where(rng.random(WORLD * SHARD) < 0.9, 2,
                       rng.integers(0, WORLD, WORLD * SHARD))
    else:                    # shard 1 holds nothing, shard 3 receives nothing
        tgt = rng.integers(0, WORLD - 1, WORLD * SHARD)
        live[1] = 0
    tgt = tgt.reshape(WORLD, SHARD)
    for s in range(WORLD):
        tgt[s, live[s]:] = WORLD       # padding rows fall off the end
    return tgt.reshape(-1).astype(np.int32), live


def _columns(rng) -> tuple:
    n = WORLD * SHARD
    a = rng.random(n)
    a[::17] = np.nan                   # null rows travel too
    words = np.array(["alpha", None, "", "z" * 11, "beta"], object)
    return (colmod.from_numpy(rng.integers(0, 5000, n).astype(np.int64)),
            colmod.from_numpy(a),
            colmod.from_numpy(words[rng.integers(0, 5, n)]))


def _exchange(ctx, cols, targets, out_cap, spec, rounds):
    def body(cc, tgt):
        out, total = shuffle_mod.shuffle_shard_ragged(
            cc, tgt, WORLD, out_cap, spec=spec, rounds=rounds)
        return out, jnp.reshape(total, (1,))

    out, totals = jax.jit(shard_map(
        body, mesh=ctx.mesh, in_specs=P(PARTITION_AXIS),
        out_specs=P(PARTITION_AXIS), check_vma=False))(
            cols, jnp.asarray(targets))
    return out, np.asarray(totals)


def _rows(cols, lo: int, hi: int) -> list:
    """Rows [lo, hi) as sorted tuples of every buffer's bytes."""
    parts = []
    for c in cols:
        for buf in (c.data, c.validity, c.lengths):
            if buf is not None:
                parts.append(np.asarray(buf)[lo:hi])
    return sorted(tuple(p[i].tobytes() for p in parts)
                  for i in range(hi - lo))


@pytest.mark.parametrize("realization", ["perbuf", "packed", "compressed"])
@pytest.mark.parametrize("kind", ["uniform", "hot", "empty"])
def test_rounded_exchange_equals_whole_and_numpy(
        ragged_on_cpu, monkeypatch, rng, kind, realization):
    ctx = ragged_on_cpu
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK",
                       "0" if realization == "perbuf" else "1")
    cols = _columns(rng)
    targets, live = _targets(kind, rng)
    spec = None
    if realization == "compressed":
        spec = plane.estimate_spec(cols, WORLD, SHARD)
        assert spec is not None and spec[0][0] == "narrow"
    cm = np.stack([np.bincount(targets[s * SHARD:(s + 1) * SHARD],
                               minlength=WORLD + 1)[:WORLD]
                   for s in range(WORLD)])
    _, out_cap = shuffle_mod.plan_shuffle(cm)

    assert shuffle_mod.plan_rounds(cm, SHARD) == (1, SHARD)
    whole, whole_totals = _exchange(ctx, cols, targets, out_cap, spec, None)

    monkeypatch.setattr(shuffle_mod, "_RAGGED_OPERAND_LIMIT", SMALL_LIMIT)
    rounds, operand_rows = shuffle_mod.plan_rounds(cm, SHARD)
    assert rounds == -(-cm.max() // 8) > 1 and operand_rows == rounds * 32
    rounded, totals = _exchange(ctx, cols, targets, out_cap, spec, rounds)

    np.testing.assert_array_equal(totals, cm.sum(axis=0))
    np.testing.assert_array_equal(whole_totals, totals)
    for t in range(WORLD):
        lo, hi = t * out_cap, t * out_cap + totals[t]
        sent = np.flatnonzero(targets == t)
        expected = sorted(
            row for i in sent for row in _rows(cols, i, i + 1))
        assert _rows(rounded, lo, hi) == _rows(whole, lo, hi) == expected
        # past its rows a shard is what the whole exchange leaves there
        assert _rows(rounded, hi, (t + 1) * out_cap) == _rows(
            whole, hi, (t + 1) * out_cap)
        assert not np.asarray(rounded[0].validity)[hi:(t + 1) * out_cap].any()


@pytest.mark.parametrize("rounded", [False, True], ids=["whole", "rounds"])
@pytest.mark.parametrize("realization_", ["packed", "compressed"])
@pytest.mark.parametrize("kind", ["uniform", "hot", "empty"])
def test_the_plane_rides_the_target_sort_to_the_same_shards(
        ragged_on_cpu, monkeypatch, realize, rng, kind, realization_,
        rounded):
    """Where permutations sort, the packed exchange carries its plane's
    words through the sort that groups them by target instead of taking
    the plane through that sort's permutation: every shard it returns
    holds the rows, in the order and to the bit, of the exchange that
    gathers the plane (the ``scatter`` row's)."""
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", "1")
    cols = _columns(rng)
    targets, _ = _targets(kind, rng)
    spec = None
    if realization_ == "compressed":
        spec = plane.estimate_spec(cols, WORLD, SHARD)
    cm = np.stack([np.bincount(targets[s * SHARD:(s + 1) * SHARD],
                               minlength=WORLD + 1)[:WORLD]
                   for s in range(WORLD)])
    _, out_cap = shuffle_mod.plan_shuffle(cm)
    rounds = None
    if rounded:
        monkeypatch.setattr(shuffle_mod, "_RAGGED_OPERAND_LIMIT", SMALL_LIMIT)
        rounds, _ = shuffle_mod.plan_rounds(cm, SHARD)
        assert rounds > 1
    got = {}
    for permute in ("scatter", "sort"):
        with realize(realization.current()._replace(permute=permute)):
            got[permute] = _exchange(ragged_on_cpu, cols, targets, out_cap,
                                     spec, rounds)
    (gathered, totals), (carried, totals_sorted) = got["scatter"], got["sort"]
    np.testing.assert_array_equal(totals, cm.sum(axis=0))
    np.testing.assert_array_equal(totals_sorted, totals)
    for a, b in zip(jax.tree.leaves(gathered), jax.tree.leaves(carried)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_a_large_shard_without_a_round_count_is_refused(
        ragged_on_cpu, small_limit, rng):
    from cylon_tpu.status import Code, CylonError

    cols = _columns(rng)
    targets, _ = _targets("uniform", rng)
    with pytest.raises(CylonError) as err:
        _exchange(ragged_on_cpu, cols, targets, SHARD, None, None)
    assert err.value.code == Code.CapacityError


def _frames(rng, n: int):
    left = pd.DataFrame({"k": rng.integers(0, n, n).astype(np.int64),
                         "a": rng.random(n)})
    right = pd.DataFrame({"k": rng.integers(0, n, n).astype(np.int64),
                          "b": rng.random(n)})
    return left, right


@pytest.mark.parametrize("permute", ["scatter", "sort"])
@pytest.mark.parametrize("pack", ["0", "1"], ids=["perbuf", "compressed"])
def test_join_groupby_sort_through_rounds_equals_pandas(
        ragged_on_cpu, small_limit, monkeypatch, realize, rng, pack,
        permute):
    """The benchmark's query on the 4-device mesh: every exchange of it
    goes in rounds, each planned from one host sync.  Where permutations
    sort, as on a TPU, every word of each packed plane rides its
    exchange's target sort and none is taken."""
    ctx = ragged_on_cpu
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", pack)
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_COMPRESS", pack)
    n = 3000
    left, right = _frames(rng, n)
    lt = Table.from_pandas(left, ctx=ctx)
    rt = Table.from_pandas(right, ctx=ctx)
    assert lt.shard_capacity * shuffle_mod.RAGGED_ROW_BYTES >= SMALL_LIMIT

    before = dict(obs_metrics.snapshot()["counters"])
    with realize(realization.current()._replace(permute=permute)):
        out = (lt.distributed_join(rt, on="k", how="inner")
               .groupby("l_k", {"a": ["sum", "mean", "count"]})
               .distributed_sort(["count_a", "l_k"],
                                 ascending=[False, True]))
        got = out.to_pandas()
    after = obs_metrics.snapshot()["counters"]

    def grew(name):
        return after.get(name, 0) - before.get(name, 0)

    assert grew("shuffle.exchanges") == 4
    assert grew("shuffle.rounds") > 4 * 3
    assert grew("shuffle.operand_bytes") >= (
        grew("shuffle.rounds") * WORLD * 32 * shuffle_mod.RAGGED_ROW_BYTES)
    rode, taken = grew("shuffle.payload_lanes"), grew("shuffle.take_lanes")
    if pack == "0":
        assert rode == taken == 0
    elif permute == "sort":
        assert rode >= 4 * 2 and taken == 0
    else:
        assert rode == 0 and taken >= 4 * 2

    merged = left.merge(right[["k"]], on="k")
    exp = merged.groupby("k")["a"].agg(["sum", "mean", "count"]).reset_index()
    exp = exp.sort_values(["count", "k"], ascending=[False, True])
    np.testing.assert_array_equal(got["l_k"].to_numpy(), exp["k"].to_numpy())
    np.testing.assert_array_equal(got["count_a"].to_numpy(),
                                  exp["count"].to_numpy())
    np.testing.assert_allclose(got["sum_a"].to_numpy(), exp["sum"].to_numpy(),
                               rtol=1e-9)
    np.testing.assert_allclose(got["mean_a"].to_numpy(),
                               exp["mean"].to_numpy(), rtol=1e-9)


def test_an_exchange_in_rounds_takes_one_plan_sync(
        ragged_on_cpu, small_limit, rng):
    from cylon_tpu.obs import spans as obs_spans

    left, _ = _frames(rng, 2000)
    table = Table.from_pandas(left, ctx=ragged_on_cpu)
    syncs = obs_metrics.counter_value("host.syncs")
    plans = obs_spans.aggregate_report().get("shuffle.plan", (0.0, 0))[1]
    rounds = obs_metrics.counter_value("shuffle.rounds")
    out = table.shuffle(["k"])
    assert out.row_count == 2000      # one more sync, counted apart below
    assert obs_metrics.counter_value("shuffle.rounds") - rounds > 1
    assert obs_spans.aggregate_report()["shuffle.plan"][1] - plans == 1
    assert obs_metrics.counter_value("host.syncs") - syncs == 2
