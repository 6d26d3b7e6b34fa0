"""Worker for the durable-execution crash-resume tests (NOT a pytest
module).  Runs one deterministic chunked join+groupby with whatever
``CYLON_TPU_*`` knobs the parent put in the environment (durable dir,
fault plan) and writes the result + stats to the given paths — so the
parent can ``kill -9`` it mid-journal (the ``killhard`` fault kind does
the killing from inside, which is indistinguishable) and then re-invoke
it in a FRESH process to prove the journal resumes the run bit-identically.

Usage: python -m tests.durable_worker <out.npz> <stats.json> [seed]
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

from cylon_tpu.exec import chunked_join_groupby_tables  # noqa: E402

N_ROWS = 4000
N_PASSES = 4


def inputs(seed: int):
    """Deterministic inputs — every invocation (killed, resumed, or
    uninterrupted) sees identical data, so the run fingerprint agrees."""
    rng = np.random.default_rng(seed)
    left = {"k": rng.integers(0, N_ROWS, N_ROWS).astype(np.int64),
            "a": rng.random(N_ROWS).astype(np.float32)}
    right = {"k": rng.integers(0, N_ROWS, N_ROWS).astype(np.int64),
             "b": rng.random(N_ROWS).astype(np.float32)}
    return left, right


def main() -> int:
    out_path, stats_path = sys.argv[1], sys.argv[2]
    seed = int(sys.argv[3]) if len(sys.argv) > 3 else 7
    left, right = inputs(seed)
    res, stats = chunked_join_groupby_tables(
        left, right, on="k", how="inner", group_by="l_k",
        agg={"a": ["sum"], "b": ["mean"]}, passes=N_PASSES, mode="hash")
    order = np.argsort(res["l_k"], kind="stable")
    np.savez(out_path, **{k: np.asarray(v)[order] for k, v in res.items()})
    with open(stats_path, "w", encoding="utf-8") as fh:
        json.dump({k: v for k, v in stats.items()
                   if isinstance(v, (int, float, str, list))}, fh)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
