"""The least time the chips could take for the query's filters with their
compactions and its expressions -- the bytes the reference's
``stage_bytes`` counts from its own rows in and out of each logical stage,
which ride on each query's ``work_bytes``, at the peak HBM bandwidth --
over the device time of the planner's stage programs (``jit_plan_*``) per
query.  HBM-bound.  Those programs hold the last join and the aggregate
too, where the planner fuses them: the share is then of everything the
stage programs do."""


def read(run):
    t = run.trace
    stages = [w.stage_bytes for w in run.work_bytes
              if hasattr(w, "stage_bytes")]
    if not t or not stages:
        return None
    secs = sum(s for module, s in t["modules_s"].items()
               if module.startswith("jit_plan_"))
    if not secs:
        return None
    least_s = (sum(stages) / len(stages)) / (
        run.peaks["hbm_bytes_per_s"] * t["chips"])
    return 100.0 * least_s / (secs / t["queries"])
