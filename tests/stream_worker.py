"""Worker for the streaming crash-resume tests (NOT a pytest module).

Drives one deterministic StreamTable session — append three fixed
micro-batches, refreshing the same incremental group-by after each —
with whatever ``CYLON_TPU_*`` knobs the parent put in the environment.
Two uses:

* ``--append-only`` with a killhard fault plan: the parent arms
  ``journal_commit@3=killhard`` so the process dies INSIDE the third
  append's spill/manifest window (indistinguishable from ``kill -9``
  mid-append) — the batch's spill is durable, its manifest line is not.
* the full driver in a FRESH process: the first two appends replay as
  idempotent no-ops from the journal, the torn third lands as a new
  committed batch, and every refresh must be bit-identical to a cold
  recompute over the frozen batch log.

Writes the final refresh frame (npz) + a stats/counters JSON so the
parent asserts delta-only execution (``rows_delta`` == batch rows,
``plan_cache.miss == 0`` on the reused plan) from the artifacts.

Usage: python -m tests.stream_worker <out.npz> <stats.json> [--append-only]
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cylon_tpu.obs import metrics as obs_metrics  # noqa: E402
from cylon_tpu.stream import GroupByQuery, StreamTable  # noqa: E402

ROWS = 16  # same-shaped batches -> the refresh plan recompiles nothing


def batches():
    """Three deterministic micro-batches — every invocation (killed,
    resumed, or golden) sees identical data, so content fingerprints and
    the journal replay agree."""
    rng = np.random.default_rng(19)
    out = []
    for _ in range(3):
        out.append({"k": rng.integers(0, 6, ROWS).astype(np.int64),
                    "v": rng.random(ROWS)})
    return out


def main() -> int:
    out_path, stats_path = sys.argv[1], sys.argv[2]
    append_only = "--append-only" in sys.argv[3:]
    s = StreamTable("killhard-stream")
    if append_only:
        for b in batches():
            s.append(b)  # the fault plan kills us inside one of these
        return 0
    q = None
    frame = None
    per_refresh = []
    for b in batches():
        s.append(b)
        if q is None:  # queries need the schema the first append fixes
            q = GroupByQuery(s, ["k"], {"v": ["sum", "mean", "count"]})
        miss0 = obs_metrics.counter_value("plan_cache.miss")
        delta0 = obs_metrics.counter_value("stream.rows_delta")
        frame, stats = q.refresh()
        per_refresh.append({
            "watermark": stats["watermark"], "mode": stats["mode"],
            "parts_run": stats["parts_run"],
            "partial_rows": stats["partial_rows"],
            "passes_skipped": stats["passes_skipped"],
            "plan_cache_miss": obs_metrics.counter_value("plan_cache.miss")
            - miss0,
            "rows_delta": obs_metrics.counter_value("stream.rows_delta")
            - delta0,
        })
    np.savez(out_path, **{k: np.asarray(v) for k, v in frame.items()})
    with open(stats_path, "w", encoding="utf-8") as fh:
        json.dump({"refreshes": per_refresh,
                   "watermark": s.watermark,
                   "batch_rows": s.batch_rows(),
                   "batches_appended": obs_metrics.counter_value(
                       "stream.batches_appended")}, fh)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
