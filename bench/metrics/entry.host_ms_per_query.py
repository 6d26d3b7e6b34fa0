"""Time of the traced queries' spans in which no operation ran on the
idlest chip, per query: what the host adds to the device's work."""


def read(run):
    t = run.trace
    if not t:
        return None
    return (t["query_s"] - t["busy_s_min"]) / t["queries"] * 1e3
