"""Device time in the planner's stage programs (``jit_plan_filter``,
``jit_plan_derive``, ``jit_plan_join_count``, ``jit_plan_fused``), mean
over the chips, per traced query.  A program whose plan stages are not
programs of that name has nothing to read."""


def read(run):
    t = run.trace
    if not t:
        return None
    secs = sum(s for module, s in t["modules_s"].items()
               if module.startswith("jit_plan_"))
    return secs / t["queries"] * 1e3 if secs else None
