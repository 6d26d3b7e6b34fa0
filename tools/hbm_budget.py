"""HBM budget model for the fused join+groupby pipeline.

Lowers the fused program (join_gather key_grouped + pipeline groupby)
at a ladder of sizes and prints XLA's own memory analysis
(argument/output/temp bytes), then bytes-per-input-row — the model that
predicts where one static program stops fitting a 16 GB v5e chip and the
out-of-core chunked driver (cylon_tpu/exec.py) must take over.

Usage: python tools/hbm_budget.py [sizes...]   (defaults 2^20..2^24)
Runs on whatever backend the process gets (CPU analysis scales linearly
and matches the TPU program's buffer plan up to layout padding).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_fused_pipeline(out_cap: int, algo: str = "sort"):
    """The fused program this model lowers:
    key_grouped inner join + boundary-scan pipeline group-by, with
    projection pushdown skipping the unused right-key output column's
    out_cap-sized gather.  Reference driver shape:
    cpp/src/examples/bench/table_join_dist_test.cpp:28-137."""
    import jax

    from cylon_tpu.config import JoinType
    from cylon_tpu.ops import groupby as groupby_mod
    from cylon_tpu.ops import join as join_mod
    from cylon_tpu.ops.groupby import AggOp

    @jax.jit
    def pipeline(cl, cnt_l, cr, cnt_r):
        joined, jm = join_mod.join_gather(cl, cnt_l, cr, cnt_r,
                                          (0,), (0,), JoinType.INNER,
                                          out_cap, algo, key_grouped=True,
                                          project=(0, 1, 3))
        gcols, g = groupby_mod.pipeline_groupby(
            joined, jm, (0,), ((1, AggOp.SUM), (2, AggOp.MEAN)), 0)
        return gcols[1].data, gcols[2].data, g, jm

    return pipeline


def analyze(rows: int, algo: str = "sort") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import cylon_tpu  # noqa: F401
    from cylon_tpu import column as colmod
    from cylon_tpu.config import JoinType
    from cylon_tpu.ops import join as join_mod
    from cylon_tpu.table import _cap_round

    rng = np.random.default_rng(1)
    lk = rng.integers(0, rows, rows).astype(np.int32)
    cols_l = (colmod.from_numpy(lk),
              colmod.from_numpy(rng.random(rows).astype(np.float32)))
    cols_r = (colmod.from_numpy(rng.integers(0, rows, rows).astype(np.int32)),
              colmod.from_numpy(rng.random(rows).astype(np.float32)))
    count = jnp.asarray(rows, jnp.int32)
    # the ~1:1 key distribution yields ~1.0x join expansion
    m = int(join_mod.join_row_count(cols_l, count, cols_r, count,
                                    (0,), (0,), JoinType.INNER, algo))
    out_cap = _cap_round(m)

    compiled = (make_fused_pipeline(out_cap, algo)
                .lower(cols_l, count, cols_r, count).compile())
    ma = compiled.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    tmp = int(ma.temp_size_in_bytes)
    peak = arg + out + tmp
    return {"rows_per_side": rows, "join_rows": m, "out_cap": out_cap,
            "argument_bytes": arg, "output_bytes": out, "temp_bytes": tmp,
            "peak_bytes": peak,
            "bytes_per_input_row": round(peak / (2 * rows), 1)}


def main() -> int:
    os.environ.setdefault("CYLON_TPU_ACCUM", "narrow")  # the TPU config
    sizes = ([int(s) for s in sys.argv[1:]]
             or [1 << 20, 1 << 22, 1 << 24])
    for rows in sizes:
        print(json.dumps(analyze(rows)), flush=True)
    return 0


if __name__ == "__main__":
    return_code = main()
    sys.exit(return_code)
