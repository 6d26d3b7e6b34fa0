"""The system under test for configuration files with ``"driver":
"join_gbs"``: two tables resident on the cell's chips, and one query =
distributed_join -> groupby -> distributed_sort through the public Table
API, its result fetched to the host as NumPy columns."""
from __future__ import annotations

import numpy as np


def build(ctx, cfg: dict, data: dict) -> dict:
    """The tables in buffers of the configuration's ``table_capacity``
    rows (a table's own row count where it names none)."""
    from cylon_tpu import Table

    state = {side: Table.from_numpy(list(cols), list(cols.values()), ctx=ctx,
                                    capacity=cfg.get("table_capacity"))
             for side, cols in data.items()}
    state["ctx"] = ctx
    return state


def run(state: dict, query: dict):
    joined = state["left"].distributed_join(state["right"], on="k",
                                            how="inner")
    grouped = joined.groupby("l_k", {"a": ["sum", "mean", "count"]})
    return grouped.distributed_sort(["count_a", "l_k"],
                                    ascending=[False, True])


def fetch(table) -> dict:
    return table.to_numpy()


def structure(state: dict, chips: int, counters: dict) -> dict:
    """What a comparison of answers cannot see: that the tables lie on
    ``chips`` distinct devices, and that the exchange ran and was the
    ragged one.  Each is a count that has to be 0."""
    out = {"shards_misplaced": sum(_misplaced(state[side], chips)
                                   for side in ("left", "right"))}
    if chips > 1:
        from cylon_tpu.context import ctx_cache

        ragged = ctx_cache(state["ctx"], "_ragged_probe").get("ragged")
        out["exchange_not_ragged"] = 0 if ragged is True else 1
        out["queries_without_exchange"] = int(
            counters["queries"] - min(counters["queries"],
                                      counters["shuffle.exchanges"]))
    return out


def _misplaced(table, chips: int) -> int:
    """Buffers of ``table`` that are not split a ``chips``-th each over
    ``chips`` distinct devices (chip_smoke.assert_sharded, counted)."""
    bad = 0
    cap = table.capacity
    for col in table.columns:
        for buf in (col.data, col.validity, col.lengths):
            if buf is None:
                continue
            shards = buf.addressable_shards
            if len({s.device for s in shards}) != chips or any(
                    s.data.shape[0] * chips != cap for s in shards):
                bad += 1
    return bad


def modes() -> dict:
    """What the program's trace-time 'auto' defaults resolved to, for the
    log (chip_smoke.realized_modes)."""
    from cylon_tpu import precision
    from cylon_tpu.ops import compact, segments
    from cylon_tpu.parallel import plane

    return {"accumulation": precision.accumulation_mode(),
            "permute": compact.permute_mode(),
            "shuffle_pack": plane.pack_enabled(),
            "shuffle_compress": plane.compress_enabled(),
            "segsum": segments.effective_mode(),
            "pallas_native": precision.on_tpu()}
