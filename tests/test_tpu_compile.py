"""Ask the chip's compiler, without the chip: the main path's kernels and
step programs must compile for a TPU v5e at real sizes.

The TPU compiler is installed wherever JAX's TPU support is, and compiles
for a chip that is described and not attached.  It refuses what interpret
mode and XLA:CPU accept — 64-bit index maps and rotate amounts under
``jax_enable_x64``, slices off the tiling, more VMEM than a kernel may use,
programs past 16 GB — so each case here guards the next PR at no chip time.
A compile that passes is not a chip run; ``chip_smoke.py`` is.

This is the only file that describes the chip, and it does so inside a
fixture: only one process may load the TPU's library, every xdist worker
imports every test file, and a second file would land on another worker.
Code under test asks ``jax.default_backend()`` and sees the CPU, so the
tests steer it with what exists — ``precision.on_tpu`` patched true resolves
every "auto" default the way the chip does — and with no option of the
program.
"""
import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cylon_tpu import dtypes, precision
from cylon_tpu.column import Column
from cylon_tpu.context import PARTITION_AXIS
from cylon_tpu.obs import STAGES
from cylon_tpu.ops import compact, pallas_kernels, pallas_scan, segments

ROWS = 1 << 24
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_chip(monkeypatch):
    """Resolve every backend-aware default as the chip does: narrow
    accumulation, sort permutes, packed + compressed plane, native Pallas
    scans and hash-partition.  The modes are read at trace time, so the jit
    caches are dropped on the way in and out."""
    monkeypatch.setattr(precision, "on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _col(n, dt, logical, sharding, width=None):
    shape = (n,) if width is None else (n, width)
    lengths = None if width is None else jax.ShapeDtypeStruct(
        (n,), jnp.int32, sharding=sharding)
    return Column(jax.ShapeDtypeStruct(shape, dt, sharding=sharding),
                  jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=sharding),
                  lengths, logical)


def _kv(n, sharding):
    return (_col(n, jnp.int32, dtypes.int32, sharding),
            _col(n, jnp.float32, dtypes.float_, sharding))


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)


@pytest.mark.parametrize("world", [4, 8])
@pytest.mark.parametrize("nwords", [(1,), (2,)], ids=["1word", "2words"])
def test_hash_partition_kernel_compiles(one_chip, nwords, world):
    flat = tuple(jax.ShapeDtypeStruct((ROWS,), jnp.uint32, sharding=one_chip)
                 for _ in range(sum(nwords)))
    compiled = pallas_kernels._hash_partition_padded.lower(
        flat, nwords, world, False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32],
                         ids=["f32", "i32"])
@pytest.mark.parametrize("segmented", [False, True],
                         ids=["plain", "segmented"])
def test_scan_kernels_compile(one_chip, segmented, dtype, op):
    lanes = ROWS // pallas_scan._SUBLANES
    x2 = jax.ShapeDtypeStruct((pallas_scan._SUBLANES, lanes), dtype,
                              sharding=one_chip)
    if segmented:
        r2 = jax.ShapeDtypeStruct(x2.shape, jnp.uint32, sharding=one_chip)
        lowered = pallas_scan._segmented_scan_padded.lower(
            x2, r2, op, pallas_scan._BLOCK_LANES, False)
    else:
        lowered = pallas_scan._scan_padded.lower(
            x2, op, pallas_scan._BLOCK_LANES, False)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("log2_rows", [22, 24])
def test_entry_step_compiles(one_chip, as_on_chip, log2_rows):
    """join_gather + hash_groupby, the step every distributed op reduces to
    per shard, at a ~1:1 join's output capacity."""
    import __graft_entry__

    rows = 1 << log2_rows
    step, _ = __graft_entry__.entry(rows=8, out_capacity=rows + rows // 8)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(step).lower(_kv(rows, one_chip), count,
                                   _kv(rows, one_chip), count).compile()
    assert precision.narrow() and segments.effective_mode() == "pallas"
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES
    # the join sends two lanes through an index to expand (ops/join.py::
    # _expansion; four on PR 29's tree) and its validity as bits of a word,
    # never as a vector.  The expansion's two takes have one signature, so
    # they share one lowered function and the compiled text names them
    # "gather" and no stage: they are counted under both names.
    stages = _gather_stages(compiled)
    assert 0 < stages["join.expand"] + stages["gather"] <= 2
    assert stages["join.gather_left"] == 3     # key, value, validity word
    # the key-ordered right rows ride the partition's sort (PR 31 took them
    # out of the combined permutation, from HBM), and the group-by's key
    # with its validity the compaction of the group starts: no ``pred``
    # vector goes through an index in the whole step
    assert stages["join.ranges"] == stages["groupby.keys"] == 0
    assert not _validity_gathers(compiled)
    # what bench/metrics/kernels.gather_ms_per_query.py reads by name: a
    # bare custom fusion is a gather, one each, and every gather is in one
    held = _custom_fusion_gathers(compiled)
    assert held and set(held.values()) == {1}
    assert all(re.fullmatch(r"%fusion(\.\d+)?", name) for name in held)
    assert sum(held.values()) == sum(stages.values())


def _gather_stages(compiled) -> collections.Counter:
    """The gather instructions of the compiled program, counted by stage
    (``obs.STAGES``, else the whole ``op_name``)."""
    stages = collections.Counter()
    for line in compiled.as_text().splitlines():
        if re.search(r"= \S+ gather\(", line):
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            named = [part for part in op_name.split("/") if part in STAGES]
            stages[named[-1] if named else op_name] += 1
    return stages


def _custom_fusion_gathers(compiled) -> dict:
    """{name of each ``kind=kCustom`` fusion: the ``gather`` instructions
    of the computation it calls}."""
    lines = compiled.as_text().splitlines()
    bodies, body = {}, None
    for line in lines:
        head = re.match(r"(%[\w.\-]+) \(.*\{$", line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        elif body is not None:
            body.append(line)
    held = {}
    for line in lines:
        if "kind=kCustom" in line:
            called = re.search(r"calls=(%[\w.\-]+)", line).group(1)
            held[line.split(" = ")[0].split()[-1]] = sum(
                1 for inner in bodies[called]
                if re.search(r"= \S+ gather\(", inner))
    return held


def _validity_gathers(compiled) -> list:
    """The gather instructions that move a ``pred`` vector."""
    return [line for line in compiled.as_text().splitlines()
            if re.search(r"= pred\[\S* gather\(", line)]


@pytest.mark.parametrize("program", [
    "sort_rows", "hash_groupby", "unique", "sort_permute",
    pytest.param("sort_rows_int64", marks=pytest.mark.slow),
    pytest.param("hash_groupby_int64", marks=pytest.mark.slow)])
def test_local_kernels_compile(one_chip, as_on_chip, program):
    """The rows ride the sorts: ``sort_rows`` compiles with no gather at
    all, ``hash_groupby`` with none but the reductions' through the
    segment ends (its keys ride the sort and the compaction of the group
    starts).  The ``int64`` cases are the benchmark
    cell's shapes (int64 key, float64 or float32 values): minutes each,
    for the table of forms in PERF.md, by hand."""
    from cylon_tpu.ops import groupby as groupby_mod
    from cylon_tpu.ops import sort as sort_mod
    from cylon_tpu.ops import unique as unique_mod

    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    kv = _kv(ROWS, one_chip)
    if program.endswith("_int64"):
        kv = (_col(ROWS, jnp.int64, dtypes.int64, one_chip),
              _col(ROWS, jnp.float64, dtypes.double, one_chip))
    assert compact.permute_mode() == "sort"
    gathers = None
    if program.startswith("sort_rows"):
        if program.endswith("_int64"):     # [count desc, key asc], 4 columns
            kv = (kv[0], _col(ROWS, jnp.float32, dtypes.float_, one_chip),
                  _col(ROWS, jnp.float32, dtypes.float_, one_chip),
                  _col(ROWS, jnp.int32, dtypes.int64, one_chip))
        by, ascending = ((3, 0), (False, True)) if len(kv) == 4 else (
            (0,), (True,))
        lowered = jax.jit(lambda c, n: sort_mod.sort_rows(
            c, n, by, ascending, True)).lower(kv, count)
        gathers = set()
    elif program.startswith("hash_groupby"):
        lowered = groupby_mod.hash_groupby.lower(
            kv, count, key_idx=(0,), aggs=tuple(
                (1, op) for op in (groupby_mod.AggOp.SUM,
                                   groupby_mod.AggOp.MEAN,
                                   groupby_mod.AggOp.COUNT)))
        gathers = {"groupby.reduce"}
    elif program == "unique":
        lowered = unique_mod.unique.lower(kv, count, (0,), "first")
    else:
        mask = jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=one_chip)
        lowered = jax.jit(compact.partition_indices).lower(mask)
    compiled = lowered.compile()
    assert _device_bytes(compiled) < HBM_BYTES
    if gathers is not None:
        assert set(_gather_stages(compiled)) <= gathers


def test_plan_filter_stage_compiles_with_literal_operands(
        one_chip, as_on_chip, monkeypatch):
    """TPC-H Q5's date range over the orders at SF 10 (2^24-row buffers,
    bench/configs/tpch_q5.json) as the planner launches it: one program
    named ``plan_filter`` whose two literals are scalar operands, so no
    date compiles it again; the date column the predicate alone reads goes
    through no compaction, and the kept columns ride the compaction's own
    sort (``keys.compact_columns``): the program holds no gather."""
    from types import SimpleNamespace

    from cylon_tpu import CylonContext, Table
    from cylon_tpu import table as table_mod
    from cylon_tpu.plan import col, executor

    ctx = CylonContext.Init()
    names = ("o_orderkey", "o_custkey", "o_orderdate")
    orders = Table(tuple(_col(ROWS, jnp.int32, dtypes.int32, one_chip)
                         for _ in names),
                   jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
                   names, ctx)
    launched = {}

    def launch(ctx, fn, *tables, key, name, operands):
        launched.update(fn=fn, tables=tables, key=key, name=name,
                        operands=operands)
        return tables[0]

    monkeypatch.setattr(table_mod, "_shard_wise", launch)
    pred = (col("o_orderdate") >= 731) & (col("o_orderdate") < 1096)
    ex = executor._Executor(None, SimpleNamespace(root=None, world=1), ctx,
                            None)
    ex._filter_table(orders, pred, names[:2])
    assert launched["name"] == "plan_filter"
    assert launched["operands"] == (731, 1096)
    assert "731" not in repr(launched["key"])
    lowered = jax.jit(launched["fn"]).lower(orders, *launched["operands"])
    scalars = [a for a in jax.tree_util.tree_leaves(lowered.args_info)
               if a.shape == ()]
    assert len(scalars) == 2
    compiled = lowered.compile()
    assert _device_bytes(compiled) < HBM_BYTES
    # two columns and one validity word each through the compaction: the
    # date is compared and dropped
    out = compiled.output_shardings
    assert len(jax.tree_util.tree_leaves(out)) == 2 * 2 + 1
    assert not _gather_stages(compiled)
    assert not _validity_gathers(compiled)
    assert not any(_custom_fusion_gathers(compiled).values())
    # the packed word, the two kept columns' data, one word of validity
    sort, = [line.split(" sort(")[0]
             for line in compiled.as_text().splitlines() if " sort(" in line]
    assert sort.count(f"[{ROWS}]") == 4


@pytest.mark.parametrize("with_string", [False, True],
                         ids=["i32_f32", "i32_f32_str"])
def test_ragged_shuffle_compiles_on_four_chips(topo, as_on_chip, with_string):
    """shuffle_shard_ragged under shard_map on the four described devices,
    over the packed and compressed plane; 2^22 rows a shard is the
    smallest shard that goes in rounds."""
    from cylon_tpu.parallel import plane, shuffle
    from cylon_tpu.utils import shard_map

    world, shard = 4, 1 << 22
    mesh = Mesh(np.array(topo.devices), (PARTITION_AXIS,))
    sharded = NamedSharding(mesh, P(PARTITION_AXIS))
    cols = _kv(world * shard, sharded)
    stats = [0, ROWS - 1]                      # observed int32 key range
    if with_string:
        cols += (_col(world * shard, jnp.uint8, dtypes.string, sharded,
                      width=32),)
        stats += [12, 12, shard]               # extent, max length, distinct
    assert plane.pack_enabled() and plane.compress_enabled()
    spec = plane.build_spec(cols, stats, world, shard)
    assert spec is not None and spec[0][0] == "narrow"
    targets = jax.ShapeDtypeStruct((world * shard,), jnp.int32,
                                   sharding=sharded)

    rounds, _ = shuffle.plan_rounds(np.full((world, world), shard // world),
                                    shard)
    assert rounds == 2

    def body(cc, tgt):
        out, total = shuffle.shuffle_shard_ragged(cc, tgt, world, 2 * shard,
                                                  spec=spec, rounds=rounds)
        return out, jnp.reshape(total, (1,))

    compiled = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(PARTITION_AXIS),
        out_specs=P(PARTITION_AXIS), check_vma=False)).lower(
            cols, targets).compile()
    assert "ragged-all-to-all" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES
    _assert_the_plane_rides_the_target_sort(
        compiled, shard, plane.plane_words(cols, spec))


def _assert_the_plane_rides_the_target_sort(compiled, shard: int,
                                            words: int) -> None:
    """No bare custom fusion gathers the ``u32[shard, words]`` plane, and
    the exchange's target sort (stage ``compact.partition``) carries the
    plane's words beside its packed key: ``1 + words`` arrays of a shard."""
    held = _custom_fusion_gathers(compiled)
    lines = compiled.as_text().splitlines()
    assert not [line for line in lines if "kind=kCustom" in line
                and held[line.split(" = ")[0].split()[-1]]
                and re.search(rf"= u32\[{shard},\d+\]", line)]
    sorts = [line.split(" sort(")[0].count(f"u32[{shard}]") for line in lines
             if " sort(" in line and "compact.partition" in line]
    assert 1 + words in sorts, sorts


def test_rounded_shuffle_compiles_at_the_weak_scaling_shard(topo, as_on_chip):
    """The benchmark's four-chip cell: (k int64, a float64) at 2^24 rows a
    shard, over the collective's operand limit, so the exchange goes in
    rounds of 2^21 rows and none of its buffers is a shard in the
    collective's 512 B rows (8 GB to send and 8 GB to receive).  The
    plane's three words ride the target sort: nothing gathers the plane."""
    from cylon_tpu.parallel import plane, shuffle
    from cylon_tpu.utils import shard_map

    world, shard = 4, ROWS
    mesh = Mesh(np.array(topo.devices), (PARTITION_AXIS,))
    sharded = NamedSharding(mesh, P(PARTITION_AXIS))
    cols = (_col(world * shard, jnp.int64, dtypes.int64, sharded),
            _col(world * shard, jnp.float64, dtypes.double, sharded))
    spec = plane.build_spec(cols, [0, 64_000_000 - 1], world, shard)
    assert spec is not None and spec[0][0] == "narrow"
    cm = np.full((world, world), 16_000_000 // world + 2_000)
    rounds, operand_rows = shuffle.plan_rounds(cm, shard)
    assert rounds == 8 and operand_rows == 8 << 21
    targets = jax.ShapeDtypeStruct((world * shard,), jnp.int32,
                                   sharding=sharded)

    def body(cc, tgt):
        out, total = shuffle.shuffle_shard_ragged(cc, tgt, world, shard,
                                                  spec=spec, rounds=rounds)
        return out, jnp.reshape(total, (1,))

    compiled = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(PARTITION_AXIS),
        out_specs=P(PARTITION_AXIS), check_vma=False)).lower(
            cols, targets).compile()
    assert "ragged-all-to-all" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES
    assert plane.plane_words(cols, spec) == 3
    _assert_the_plane_rides_the_target_sort(compiled, shard, 3)


def test_skew_split_compiles_at_the_weak_scaling_shard(topo, as_on_chip):
    """The skew split of the four-chip join at 2^24 rows a shard: the hot
    set's program and the probe side's targets pass with its membership
    test.  Neither holds a ``[rows, K]`` buffer: the test of every row
    against the hot set is one reduction, its temporaries those of the
    plain targets pass (the hash, the padded key words)."""
    from cylon_tpu.parallel import collectives
    from cylon_tpu.parallel import ops as par_ops
    from cylon_tpu.parallel import shuffle
    from cylon_tpu.utils import shard_map

    world, shard, K = 4, ROWS, par_ops.SKEW_HOT_KEYS
    mesh = Mesh(np.array(topo.devices), (PARTITION_AXIS,))
    sharded = NamedSharding(mesh, P(PARTITION_AXIS))
    side = (_col(world * shard, jnp.int64, dtypes.int64, sharded),
            _col(world * shard, jnp.float64, dtypes.double, sharded))
    counts = jax.ShapeDtypeStruct((world,), jnp.int32, sharding=sharded)

    def skew_fn(lc, lk, rc, rk):
        hot, n = par_ops._hot_keys(lc, lk[0], (0,), rc, rk[0], (0,), world)
        return hot, jnp.reshape(n, (1,))

    def fn(lc, lk, hot, n):
        tt = collections.namedtuple("T", "columns row_counts")(lc, lk)
        tgt, (skew,) = par_ops._split_targets(tt, (0,), world, "hash.keep",
                                              None, (hot, n))
        cm = collectives.allgather(shuffle.target_counts(tgt, world))
        return tgt, cm.reshape(world, world), skew

    def compiled(body, *args, out_specs):
        return jax.jit(shard_map(body, mesh=mesh, in_specs=P(PARTITION_AXIS),
                                 out_specs=out_specs, check_vma=False)
                       ).lower(*args).compile()

    hot = compiled(skew_fn, side, counts, side, counts,
                   out_specs=P(PARTITION_AXIS))
    split = compiled(
        fn, side, counts,
        jax.ShapeDtypeStruct((world * K,), jnp.uint32, sharding=sharded),
        counts, out_specs=(P(PARTITION_AXIS), P(), P()))
    for program in (hot, split):
        assert program.memory_analysis().temp_size_in_bytes < shard * 16
        assert "tpu_custom_call" in program.as_text()   # the Pallas hash
