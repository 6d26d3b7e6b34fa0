"""Device time in Pallas (Mosaic custom-call) kernels, mean over the
chips, per traced query; 0 where the trace holds none."""


def read(run):
    t = run.trace
    if not t:
        return None
    return t["categories_s"].get("pallas", 0.0) / t["queries"] * 1e3
