"""The least time the chips could take for a query -- its input columns
read once and its result written once at the peak HBM bandwidth of all
the cell's chips -- over the device's busy time per query.  Defined on
the query's work, so it reads the same whatever implements the query."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"] or not run.work_bytes:
        return None
    work = sum(run.work_bytes) / len(run.work_bytes)
    least_s = work / (run.peaks["hbm_bytes_per_s"] * t["chips"])
    return 100.0 * least_s / (t["busy_s"] / t["queries"])
