"""Time the host waits for the fetch's 64-bit buffers (span
``table.fetch.d2h.wide``, inside ``table.fetch.d2h``), per completed
query.  Where every copy of a sharded fetch is in flight together the
buffers are waited for in flatten order, so this is the host's wait on a
64-bit buffer once the earlier ones have landed, not that buffer's
transfer time.  A program that records no ``obs.root`` gives nothing to
read; a window with no 64-bit buffer fetched is a measured 0."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    return run.spans.get("table.fetch.d2h.wide", (0.0, 0))[0] / queries * 1e3
