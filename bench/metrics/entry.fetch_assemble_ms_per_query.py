"""Time the host spends putting a sharded result's shard pieces into one
array a buffer (span ``table.fetch.assemble``: ``np.concatenate`` in
``Table._fetched_columns``, opened after that buffer's copies have landed),
per completed query.  A one-shard table never assembles.  A program that
records no ``obs.root`` gives nothing to read; a window without an
assembly is a measured 0."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    return run.spans.get("table.fetch.assemble", (0.0, 0))[0] / queries * 1e3
