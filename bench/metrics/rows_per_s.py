"""Input rows of every query completed in the window, over the wall time
from the window's start to the last completion (host clock)."""
from bench import traffic


def read(run):
    return traffic.rows_per_s(run.t0, run.records)
