"""Time inside the blocking device-to-host copies of the result's fetch
(span ``table.fetch.d2h``: device slice, copy, row-count read), per
completed query.  A program that records no ``obs.root`` gives nothing to
read; a window without a fetch is a measured 0."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    return run.spans.get("table.fetch.d2h", (0.0, 0))[0] / queries * 1e3
