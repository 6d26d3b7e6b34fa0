"""Shared example-harness helpers: context setup, table ingest, JSON
timing output (the role of the reference's bench drivers' logging,
cpp/src/examples/bench/table_join_dist_test.cpp:28-137)."""
from __future__ import annotations

import json
import sys


def default_ctx(world: int | None = None):
    """Distributed context over all visible devices (or ``world`` of them);
    plain local context when only one device exists."""
    import jax

    from cylon_tpu.utils.compile_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from cylon_tpu import CylonContext, TPUConfig

    n = len(jax.devices())
    w = world or n
    if w <= 1:
        return CylonContext.Init()
    return CylonContext.InitDistributed(TPUConfig(world_size=min(w, n)))


def table_from_arrays(arrays: dict, ctx):
    from cylon_tpu import Table

    return Table.from_numpy(list(arrays.keys()), list(arrays.values()),
                            ctx=ctx)


def emit(config: str, **fields) -> dict:
    rec = {"config": config, **{
        k: (round(v, 3) if isinstance(v, float) else v)
        for k, v in fields.items()}}
    print(json.dumps(rec), flush=True)
    return rec


def log(msg: str) -> None:
    print(f"[example] {msg}", file=sys.stderr, flush=True)
