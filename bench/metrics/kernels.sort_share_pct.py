"""Share of the device's busy time spent in XLA sort operations; 0 where
the trace holds none."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["categories_s"].get("sort", 0.0) / t["busy_s"]
