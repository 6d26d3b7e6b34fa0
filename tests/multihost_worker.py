"""Worker for the 2-process multi-host integration test (NOT a pytest
module).  Each process contributes 4 virtual CPU devices to one global
8-device mesh via jax.distributed.initialize — the JAX rendering of the
reference's ``mpirun -np 2`` world (cpp/test/CMakeLists.txt:19-50).

Usage: python multihost_worker.py <process_id> <num_processes> <port>
"""
import os
import sys

pid, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cylon_tpu import CylonContext, Table, TPUConfig  # noqa: E402


def main() -> int:
    try:
        ctx = CylonContext.InitDistributed(TPUConfig(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=nprocs, process_id=pid))
    except RuntimeError as e:
        # the parent's _free_port() reservation is inherently TOCTOU (the
        # port must be released for the jax coordinator to bind it): a
        # lost race surfaces here as a bind failure — report EX_TEMPFAIL
        # so the parent retries the gang on a fresh port instead of
        # failing the test
        low = str(e).lower()
        if "address already in use" in low or "bind" in low:
            print(f"proc {pid}: coordinator port race on {port}: {e}",
                  flush=True)
            return 75  # tests/test_multihost.py BIND_RACE_RC
        raise
    assert jax.process_count() == nprocs, jax.process_count()
    world = ctx.GetWorldSize()
    assert world == 4 * nprocs, world
    assert ctx.GetRank() == pid

    # identical global data on every process (the device_put sharding layer
    # slices out each host's shards)
    rng = np.random.default_rng(7)
    pl = pd.DataFrame({"k": rng.integers(0, 60, 400), "x": rng.random(400)})
    pr = pd.DataFrame({"k": rng.integers(0, 60, 300), "y": rng.random(300)})
    l = Table.from_pandas(pl, ctx=ctx)
    r = Table.from_pandas(pr, ctx=ctx)

    ctx.Barrier()

    j = l.distributed_join(r, on="k", how="inner")
    exp = len(pl.merge(pr, on="k"))
    assert j.row_count == exp, (j.row_count, exp)

    g = l.groupby("k", {"x": ["sum", "mean"]})
    assert g.row_count == pl.k.nunique(), g.row_count

    s = float(l.sum("x"))
    assert abs(s - pl.x.sum()) < 1e-6, (s, pl.x.sum())

    srt = l.distributed_sort("x")
    assert srt.row_count == len(pl)

    # host export via process_allgather: every process sees the full join
    full = j.to_pandas()
    assert len(full) == exp, len(full)

    # __setitem__ with a host value must slice shards per process
    l["z"] = np.arange(len(pl), dtype=np.int64)
    assert int(l.sum("z")) == int(np.arange(len(pl), dtype=np.int64).sum())

    # per-shard write is GATHER-FREE: this process must write exactly its
    # own 4 shards (reference: rank-local WriteCSV, table.cpp:243-256)
    import glob
    import tempfile

    outdir = os.path.join(tempfile.gettempdir(), f"mh_shards_{port}")
    os.makedirs(outdir, exist_ok=True)
    shards = l._addressable_host_shards()
    assert [sid for sid, _, _ in shards] == list(range(4 * pid, 4 * pid + 4)), \
        [sid for sid, _, _ in shards]
    l.to_csv(os.path.join(outdir, "part_{shard}.csv"), per_shard=True)
    mine = sorted(glob.glob(os.path.join(outdir, "part_*.csv")))
    ctx.Barrier()  # wait until both processes finished writing
    allf = sorted(glob.glob(os.path.join(outdir, "part_*.csv")))
    assert len(mine) >= 4 and len(allf) == 8, (len(mine), len(allf))
    total = sum(len(pd.read_csv(f)) for f in allf)
    assert total == len(pl), (total, len(pl))

    print(f"proc {pid}/{nprocs} OK: join={exp} groups={g.row_count}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
