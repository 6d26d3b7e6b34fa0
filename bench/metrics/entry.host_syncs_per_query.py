"""Round trips in which the host reads a device value to choose the next
program (counter ``host.syncs``), per completed query.  A program that
records no ``obs.root`` span has no such counter: nothing to read."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    return run.counters.get("host.syncs", 0) / queries
