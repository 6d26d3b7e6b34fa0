"""Device time in Pallas (Mosaic custom-call) kernels, mean over the
chips, per traced query."""


def read(run):
    t = run.trace
    secs = t.get("categories_s", {}).get("pallas") if t else None
    return secs / t["queries"] * 1e3 if secs else None
