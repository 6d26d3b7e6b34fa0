"""Share of the device's busy time spent in XLA sort operations."""


def read(run):
    t = run.trace
    secs = t.get("categories_s", {}).get("sort") if t else None
    return 100.0 * secs / t["busy_s"] if secs else None
