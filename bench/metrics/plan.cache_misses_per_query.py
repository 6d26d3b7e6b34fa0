"""Stage programs built in the window (counter ``plan_cache.miss`` of the
stage cache), per completed query: 0 expected, and a literal that
compiles reads here.  Read only of a program that counts its stage
programs (``plan.stage_programs``): another has nothing to read."""


def read(run):
    queries = run.counters.get("queries")
    if "plan.stage_programs" not in run.counters or not queries:
        return None
    return run.counters.get("plan_cache.miss", 0) / queries
