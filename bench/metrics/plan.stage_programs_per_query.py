"""Stage programs the planner launched (counter ``plan.stage_programs``:
filter, derive, join-count and fused stages), per completed query.  A
program without the counter has nothing to read."""


def read(run):
    queries = run.counters.get("queries")
    if "plan.stage_programs" not in run.counters or not queries:
        return None
    return run.counters["plan.stage_programs"] / queries
