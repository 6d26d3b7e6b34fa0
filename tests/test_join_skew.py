"""The skew split of a distributed join's exchange on the 4-device CPU
mesh, under the chip's realization of the exchange: packed and compressed
plane, the ragged family (through the all-gather stand-in of
``test_shuffle_rounds``), permutations that sort.  Probe (left) rows whose
key is hot stay on their shard, build (right) rows whose key is hot go to
every shard, and every join equals pandas; RIGHT and FULL_OUTER joins keep
the plain exchange; the output is stamped hash-partitioned exactly when no
key is hot; the counters and the span; the cell's three readers; and the
guard that the split compiles one set of programs whatever the data.
"""
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pandas as pd
import pytest

from bench.drivers import join_gbs as driver
from bench.references import join_gbs as uniform_ref
from bench.references import join_gbs_zipf as ref
from bench.run import COMPILE_EVENT, load_reader
from cylon_tpu import Table, config
from cylon_tpu.context import ctx_cache
from cylon_tpu.obs import metrics as obs_metrics
from cylon_tpu.obs import spans as obs_spans
from cylon_tpu.ops import hashing, realization
from cylon_tpu.parallel import collectives
from cylon_tpu.parallel import ops as par_ops
from cylon_tpu.parallel import shuffle as shuffle_mod
from tests.conftest import _realize
from tests.test_shuffle_rounds import (SMALL_LIMIT,
                                       _ragged_all_to_all_by_all_gather)

WORLD = 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "bench", "configs",
                       "cylon_join_zipf_4chip.json")) as f:
    CONFIG = json.load(f)
#: rows a side: 2^14 rows a shard, less a little, as the cell's 16,000,000
#: of 2^24
ROWS = 4 * 15_600
R = par_ops.SKEW_BLOCK_ROWS
#: the many-to-many cases' hot key: 0.21% of the probe rows at Zipf 1.25,
#: 35 of 16,384 samples, so that its build rows times its probe rows stay
#: a small join
DUP_KEY = 40
SKEW = ("join.skew.hot_keys", "join.skew.kept_rows",
        "join.skew.replicated_rows", "join.shard_rows_max", "join.out_rows")
READERS = ("exchange.shard_balance_pct", "exchange.skew_kept_pct",
           "exchange.skew_roofline_pct")


@pytest.fixture(scope="module")
def chip(ctx4):
    """The four-device mesh with the exchange realized as on the chip."""
    probe = ctx_cache(ctx4, "_ragged_probe")
    saved = dict(probe)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(collectives, "ragged_all_to_all",
                   _ragged_all_to_all_by_all_gather)
        mp.setattr(par_ops, "_probe_ragged", lambda ctx: True)
        mp.setenv("CYLON_TPU_SHUFFLE_PACK", "1")
        mp.setenv("CYLON_TPU_SHUFFLE_COMPRESS", "1")
        probe.clear()
        with _realize(realization.current()._replace(permute="sort")):
            yield ctx4
    probe.clear()
    probe.update(saved)


def _counters(names=SKEW):
    got = obs_metrics.snapshot()["counters"]
    return np.array([got.get(n, 0) for n in names], dtype=np.int64)


def _zipf(rng, n, s, size=None):
    return (ref.zipf_ranks(rng, n, s, n if size is None else size)
            - 1).astype(np.int64)


def _frames(case: str, rng, n: int = ROWS):
    """(left, right) of one case: the left side is the probe."""
    a, b = rng.random(n), rng.random(n)
    dim = rng.permutation(n).astype(np.int64)
    if case == "zipf125":
        return pd.DataFrame({"k": _zipf(rng, n, 1.25), "a": a}), \
            pd.DataFrame({"k": dim, "b": b})
    if case == "zipf105":
        return pd.DataFrame({"k": _zipf(rng, n, 1.05), "a": a}), \
            pd.DataFrame({"k": dim, "b": b})
    if case == "uniform":
        return pd.DataFrame({"k": rng.integers(0, n, n), "a": a}), \
            pd.DataFrame({"k": rng.integers(0, n, n), "b": b})
    if case == "one_key":
        return pd.DataFrame({"k": np.full(n, 7, np.int64), "a": a}), \
            pd.DataFrame({"k": dim, "b": b})
    if case == "nulls":
        k = _zipf(rng, n, 1.25).astype(np.float64)
        k[rng.random(n) < 0.3] = np.nan        # the hottest key of all
        d = dim.astype(np.float64)
        d[:3] = np.nan
        return pd.DataFrame({"k": k, "a": a}), pd.DataFrame({"k": d, "b": b})
    if case in ("many_fits", "many_overflows"):
        # DUP_KEY is hot on the probe side and has build rows of its own:
        # fewer than the block holds over all shards, or more
        dup = R // 2 if case == "many_fits" else R + 1
        right = dim.copy()
        right[rng.choice(n, dup, replace=False)] = DUP_KEY
        return pd.DataFrame({"k": _zipf(rng, n, 1.25), "a": a}), \
            pd.DataFrame({"k": right, "b": b})
    raise ValueError(case)


def _tables(ctx, left, right, empty_shard=False):
    lt = Table.from_pandas(left, ctx=ctx)
    rt = Table.from_pandas(right, ctx=ctx)
    if empty_shard:
        # the rows of the last shard are filtered out where they lie
        cut = 3 * (-(-len(left) // WORLD))
        pos = Table.from_pandas(left.assign(pos=np.arange(len(left))),
                                ctx=ctx)
        lt = pos.select(lambda r: r["pos"] < cut).project(["k", "a"])
        assert list(np.asarray(lt.row_counts))[-1] == 0
    return lt, rt


def _assert_same_rows(got: pd.DataFrame, exp: pd.DataFrame) -> None:
    """The same rows, nulls included, in any order."""
    cols = list(exp.columns)
    np.testing.assert_array_equal(
        got[cols].sort_values(cols).to_numpy(np.float64),
        exp.sort_values(cols).to_numpy(np.float64))


def _expected(left, right, how):
    """pandas' merge in the program's output schema; pandas matches NaN
    keys with each other, and so do the program's null keys."""
    exp = left.merge(right, on="k", how=how, indicator=True)
    return pd.DataFrame({
        "l_k": exp["k"].where(exp["_merge"] != "right_only"),
        "a": exp["a"],
        "r_k": exp["k"].where(exp["_merge"] != "left_only"),
        "b": exp["b"]})


def _got(table) -> pd.DataFrame:
    return table.to_pandas()[["l_k", "a", "r_k", "b"]].astype(np.float64)


def _hot_hashes(lt, rt) -> set:
    hot, n = par_ops._hot_set(lt, rt, (0,), (0,))
    return set(np.asarray(hot)[:int(np.asarray(n)[0])].tolist())


def _hash_of(key: int) -> int:
    from cylon_tpu import column as colmod

    return int(np.asarray(hashing.hash_columns(
        [colmod.from_numpy(np.full(1, key, np.int64))]))[0])


CASES = ["zipf125", "zipf105", "uniform", "one_key", "empty_shard", "nulls",
         "many_fits", "many_overflows"]


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("case", CASES)
def test_split_join_equals_pandas(chip, rng, case, how):
    left, right = _frames("zipf125" if case == "empty_shard" else case, rng)
    lt, rt = _tables(chip, left, right, empty_shard=case == "empty_shard")
    if case == "empty_shard":
        left = lt.to_pandas()
    before = _counters()
    out = lt.distributed_join(rt, on="k", how=how)
    hot, kept, replicated, fullest, rows = _counters() - before
    exp = _expected(left, right, how)
    _assert_same_rows(_got(out), exp)
    assert rows == out.row_count == len(exp)
    if case == "uniform":
        assert (hot, kept, replicated) == (0, 0, 0)
    else:
        assert hot > 0 and kept > 0
        assert replicated % WORLD == 0 and 0 < replicated // WORLD <= R
    if case.startswith("many"):
        # the key stays hot while its build rows fit the block beside the
        # other hot keys' one each, and takes the plain exchange past it
        fits = case == "many_fits"
        assert (_hash_of(DUP_KEY) in _hot_hashes(lt, rt)) == fits
        assert (replicated // WORLD >= R // 2) == fits
    if case == "one_key":
        assert (hot, kept, replicated) == (1, ROWS, WORLD)
        # every probe row stays where it is: the join is as even as the
        # input
        assert fullest == -(-ROWS // WORLD)


def test_the_split_runs_in_rounds_too(chip, rng, monkeypatch):
    """Shards over the (shrunk) operand limit: both sides' exchanges go in
    rounds, the build side's held rows after them."""
    monkeypatch.setattr(shuffle_mod, "_RAGGED_OPERAND_LIMIT", SMALL_LIMIT)
    left, right = _frames("zipf125", rng, 4 * 500)
    lt, rt = _tables(chip, left, right)
    rounds = obs_metrics.counter_value("shuffle.rounds")
    out = lt.distributed_join(rt, on="k", how="inner")
    assert obs_metrics.counter_value("shuffle.rounds") - rounds > 2
    _assert_same_rows(_got(out), _expected(left, right, "inner"))


@pytest.mark.parametrize("how", ["right", "outer"])
def test_right_and_full_outer_keep_the_plain_exchange(chip, rng, how):
    left, right = _frames("zipf125", rng)
    lt, rt = _tables(chip, left, right)
    before = _counters()
    with config.knob_env(CYLON_TPU_TRACE="1"):
        mark = len(obs_spans.events())
        out = lt.distributed_join(rt, on="k", how=how)
        names = {e.name for e in obs_spans.events()[mark:]}
    assert "shuffle.skew" not in names
    assert list(_counters() - before)[:3] == [0, 0, 0]
    _assert_same_rows(_got(out), _expected(left, right, how))
    if how == "right":
        assert out._partitioning == ("hash", (("r_k",),), WORLD)


@pytest.mark.parametrize("case,stamped", [("zipf125", False),
                                          ("uniform", True)])
def test_stamped_hash_partitioned_exactly_when_no_key_is_hot(chip, rng, case,
                                                             stamped):
    left, right = _frames(case, rng)
    lt, rt = _tables(chip, left, right)
    hot = obs_metrics.counter_value("join.skew.hot_keys")
    out = lt.distributed_join(rt, on="k", how="inner")
    assert (obs_metrics.counter_value("join.skew.hot_keys") == hot) == stamped
    assert (getattr(out, "_partitioning", None) is not None) == stamped
    if stamped:
        assert out._partitioning == ("hash", (("l_k",), ("r_k",)), WORLD)


def test_hot_probe_rows_stay_and_the_rest_go_by_hash(chip, rng):
    """Every left shard after the exchange holds its own hot rows and the
    other rows whose hash names it: the hot set is read back from the
    device and the rows placed again on the host."""
    left, right = _frames("zipf125", rng)
    lt, rt = _tables(chip, left, right)
    hot, n = par_ops._hot_set(lt, rt, (0,), (0,))
    hot, n = np.asarray(hot).reshape(WORLD, -1), np.asarray(n)
    assert (hot == hot[0]).all() and (n == n[0]).all() and n[0] > 0
    hot = set(hot[0][:n[0]].tolist())
    h = np.asarray(hashing.hash_columns([lt.columns[0]])).reshape(WORLD, -1)
    counts = np.asarray(lt.row_counts)
    before = _counters()
    left_sh, _, n_hot = par_ops.join_exchange(lt, rt, (0,), (0,), split=True)
    assert n_hot == len(hot)
    kept, sent = 0, 0
    want = np.zeros(WORLD, np.int64)
    for s in range(WORLD):
        live = h[s][:counts[s]]
        is_hot = np.isin(live, list(hot))
        kept += int(is_hot.sum())
        sent += int((~is_hot).sum())
        want[s] += is_hot.sum()
        want += np.bincount(live[~is_hot] & (WORLD - 1), minlength=WORLD)
    assert np.array_equal(np.asarray(left_sh.row_counts), want)
    counted = int((_counters() - before)[1])
    assert counted == kept > 0
    assert counted + sent == ROWS


def test_the_split_balances_the_join_under_zipf_1p25(chip):
    """2^18 rows a shard: the fullest shard's join within 110% of the
    mean, where the plain exchange puts key 0's 22% on one shard."""
    rows = 1 << 20
    rng = np.random.default_rng(5)
    left = pd.DataFrame({"k": _zipf(rng, rows, 1.25), "a": rng.random(rows)})
    right = pd.DataFrame({"k": rng.permutation(rows).astype(np.int64),
                          "b": rng.random(rows)})
    lt, rt = _tables(chip, left, right)
    before = _counters()
    out = lt.distributed_join(rt, on="k", how="inner")
    hot, kept, replicated, fullest, rows_out = _counters() - before
    assert rows_out == out.row_count == rows
    assert 100.0 * fullest * WORLD / rows_out <= 110
    assert kept > np.sum(left["k"] == 0)
    run = SimpleNamespace(counters={"join.shard_rows_max": fullest,
                                    "join.out_rows": rows_out,
                                    "join.skew.kept_rows": kept,
                                    "queries": 1},
                          cell=_cell(rows))
    assert load_reader(READERS[0])(run) <= 110
    assert load_reader(READERS[1])(run) == pytest.approx(100.0 * kept / rows)


@pytest.mark.parametrize("trace", ["1", "0"])
def test_the_span_opens_only_under_trace(chip, rng, trace):
    left, right = _frames("zipf125", rng)
    lt, rt = _tables(chip, left, right)
    with config.knob_env(CYLON_TPU_TRACE=trace):
        mark = len(obs_spans.events())
        lt.distributed_join(rt, on="k", how="inner")
        skew = [e for e in obs_spans.events()[mark:]
                if e.name == "shuffle.skew"]
    if trace == "0":
        assert skew == []
    else:
        (span,) = skew
        assert span.attrs["hot_keys"] > 0 and span.attrs["world"] == WORLD


# ---------------------------------------------------------------------------
# the cell: its query against the plain reference, and one set of programs
# ---------------------------------------------------------------------------

def _cfg(rows, capacity=None):
    return dict(CONFIG, rows_per_side_by_chips={"4": rows},
                table_capacity=capacity)


def _cell(rows):
    return SimpleNamespace(chips=WORLD, cfg=_cfg(rows), reference=ref)


def test_the_cells_query_equals_the_reference(chip):
    cfg = _cfg(ROWS)
    data = ref.make_data(cfg, WORLD, 3000000019)
    state = driver.build(chip, cfg, data)
    (query,) = ref.queries(cfg, 7)
    got = driver.fetch(driver.run(state, query))
    exp = ref.answer(data, query)
    compared = ref.compare(got, exp)
    assert all(v <= CONFIG["limits"][n] for n, v in compared.items()), \
        compared
    assert got["l_k"][0] == 0 and exp["join_rows"] == ROWS


def _compiles(fn):
    seen = []

    def on(event, duration, **kw):
        if event == COMPILE_EVENT:
            seen.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    return seen


def test_one_set_of_programs_whatever_the_data(chip):
    """The guard: the cell's query at one size on Zipf data from one seed
    compiles its programs; on Zipf data from two other seeds it compiles
    nothing, and nor does the join of uniform data (the uniform cell's
    tables) at the same size, whose hot set is empty.  No shape follows
    how many keys are hot or how many rows they hold."""
    rows, capacity = 4 * 15_000, 4 << 14
    cfg = _cfg(rows, capacity)

    def query(seed):
        """The query to its result on the devices: the fetch slices each
        shard's rows at sizes the groups of the seed set, as before the
        split."""
        state = driver.build(chip, cfg, ref.make_data(cfg, WORLD, seed))
        return lambda: jax.block_until_ready(
            driver.run(state, {}).columns)

    first = query(11)
    assert _compiles(first)
    hot = obs_metrics.counter_value("join.skew.hot_keys")
    for seed in (12, 3000000019):
        again = query(seed)
        assert _compiles(again) == [], seed
    uniform = driver.build(chip, cfg, uniform_ref.make_data(cfg, WORLD, 13))
    joined = []
    assert _compiles(lambda: joined.append(
        uniform["left"].distributed_join(uniform["right"], on="k").row_count
    )) == []
    assert obs_metrics.counter_value("join.skew.hot_keys") > hot
    assert joined[0] > 0


# ---------------------------------------------------------------------------
# the three readers
# ---------------------------------------------------------------------------

def test_readers_on_hand_made_runs():
    rows = 64_000_000
    counters = {"queries": 6, "join.out_rows": 6 * rows,
                "join.shard_rows_max": 6 * 16_490_570,
                "join.skew.kept_rows": 6 * 44_776_000}
    run = SimpleNamespace(counters=counters, cell=_cell(rows))
    assert load_reader(READERS[0])(run) == pytest.approx(103.0660625)
    assert load_reader(READERS[1])(run) == pytest.approx(69.9625)
    # the uniform cell: nothing hot is a measured 0
    run = SimpleNamespace(counters=dict(counters, **{
        "join.skew.kept_rows": 0}), cell=_cell(rows))
    assert load_reader(READERS[1])(run) == 0
    # 512,000,000 B of build keys, 4 x 4096 x 8 B of samples and 4 x 65 x 4
    # B of hot sets, at 4 x 819 GB/s: 156.37 us, against 3 ms a query
    trace = {"queries": 3, "chips": 4,
             "modules_s": {"jit_skew_fn": 0.009, "jit_rfn": 3.0}}
    run = SimpleNamespace(trace=trace, cell=_cell(rows),
                          peaks={"hbm_bytes_per_s": 819e9})
    least = (512_000_000 + 4 * 4096 * 8 + 4 * 65 * 4) / (4 * 819e9)
    assert load_reader(READERS[2])(run) == pytest.approx(
        100.0 * least / 0.003)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_split_gives_nothing(name):
    """The parent of the split: no counters and no ``jit_skew_fn``."""
    run = SimpleNamespace(
        counters={"queries": 6, "join.out_rows": 100, "host.syncs": 54},
        cell=_cell(64_000_000), peaks={"hbm_bytes_per_s": 819e9},
        trace={"queries": 3, "chips": 4, "modules_s": {"jit_rfn": 3.0}})
    assert load_reader(name)(run) is None
    assert load_reader(name)(SimpleNamespace(
        counters={}, cell=_cell(64), trace={},
        peaks={"hbm_bytes_per_s": 819e9})) is None


def test_readers_on_a_real_query(chip):
    cfg = _cfg(ROWS)
    state = driver.build(chip, cfg, ref.make_data(cfg, WORLD, 21))
    before = dict(obs_metrics.snapshot()["counters"])
    for _ in range(2):
        driver.fetch(driver.run(state, {}))
    after = obs_metrics.snapshot()["counters"]
    counters = {k: v - before.get(k, 0) for k, v in after.items()}
    counters["queries"] = 2
    run = SimpleNamespace(counters=counters, cell=_cell(ROWS))
    balance = load_reader(READERS[0])(run)
    kept = load_reader(READERS[1])(run)
    assert 100 <= balance <= 115
    assert 40 < kept < 100
    assert counters["join.out_rows"] == 2 * ROWS
    # the split adds no host sync: the stats and the plan of each of the
    # four exchanges, and the join's capacity check
    assert counters["host.syncs"] == 2 * 9
