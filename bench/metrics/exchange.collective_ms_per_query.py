"""Device time in collective operations (ragged-all-to-all, all-to-all,
all-gather, all-reduce, ...), mean over the chips, per traced query."""


def read(run):
    t = run.trace
    secs = t.get("categories_s", {}).get("collective") if t else None
    return secs / t["queries"] * 1e3 if secs else None
