"""Megabytes (10^6 bytes) that arrived on the host in the fetch's copies
(counter ``table.fetch.bytes``), per completed query.  A program that
records no ``obs.root`` span has no such counter: nothing to read; a
window without a fetch is a measured 0."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    return run.counters.get("table.fetch.bytes", 0) / queries / 1e6
