"""Share of the traced window in which no operation ran on the idlest
chip."""


def read(run):
    t = run.trace
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s_min"] / t["window_s"])
