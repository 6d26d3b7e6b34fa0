"""Host time inside the program that waits for nothing (Python, plan
caches, tracing, dispatch), per completed query: the program's root spans
(``obs.root``) less its waits for the device (``host.sync``) and its
fetches (``table.fetch``).  A program that records no ``obs.root`` gives
nothing to read; a span that never opened in the window is a measured 0."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    root, sync, fetch = (run.spans.get(name, (0.0, 0))[0]
                         for name in ("obs.root", "host.sync", "table.fetch"))
    return (root - sync - fetch) / queries * 1e3
