"""Megabytes (10^6 bytes) the sharded fetch uploaded again after fetching
them (counter ``table.fetch.h2d_bytes``: ``Table._gathered_columns`` puts
the live rows of every shard back on a device before ``to_numpy`` copies
them out), per completed query.  A program that records no ``obs.root``
span has no such counter: nothing to read; a window whose fetches upload
nothing is a measured 0."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    return run.counters.get("table.fetch.h2d_bytes", 0) / queries / 1e6
