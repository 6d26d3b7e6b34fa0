"""Time the host spends turning fetched buffers into the result's columns
(span ``table.fetch.convert``, one a column, in ``column.to_numpy`` /
``to_arrow`` after the column's copies: widening a narrow count,
``valid.all()``, object arrays and nulls, string decoding, ``pa.array``),
per completed query.  A program that records no ``obs.root`` gives
nothing to read; a window without a conversion is a measured 0."""


def read(run):
    queries = run.counters.get("queries")
    if "obs.root" not in run.spans or not queries:
        return None
    return run.spans.get("table.fetch.convert", (0.0, 0))[0] / queries * 1e3
