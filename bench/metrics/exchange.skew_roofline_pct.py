"""The skew split's detection program against its HBM roofline: the bytes
the program ``skew_fn`` cannot avoid (``skew_bytes``) at the peak HBM
bandwidth of all the cell's chips, over its device seconds (mean over the
chips) per traced query.  A trace that holds no such program, as the
program before the skew split gives, has nothing to read."""
import numpy as np

PROGRAM = "jit_skew_fn"


def skew_bytes(cfg: dict, chips: int, sample: int, hot_keys: int) -> int:
    """What ``skew_fn`` reads and writes at the least: the probe keys of
    its stride sample on every chip, every build key once (the build
    rows of each hot key are counted in it), and every chip's copy of the
    hot set (``hot_keys`` uint32 hashes and their int32 count)."""
    probe = np.dtype(cfg["tables"]["left"]["k"]).itemsize
    build = np.dtype(cfg["tables"]["right"]["k"]).itemsize
    rows = int(cfg["rows_per_side_by_chips"][str(chips)])
    return chips * sample * probe + rows * build + chips * (hot_keys + 1) * 4


def read(run):
    t = run.trace
    secs = t.get("modules_s", {}).get(PROGRAM) if t else None
    if not secs or not t["queries"]:
        return None
    from cylon_tpu.parallel import ops

    least_s = skew_bytes(run.cell.cfg, run.cell.chips, ops.SKEW_SAMPLE,
                         ops.SKEW_HOT_KEYS) / (
        run.peaks["hbm_bytes_per_s"] * run.cell.chips)
    return 100.0 * least_s / (secs / t["queries"])
