"""The share of the probe side's rows that the skew split kept on their
own shard: 100 x counter ``join.skew.kept_rows`` (probe rows whose key was
hot) over the left table's rows in every query of the window.  It reads 0
where no key is hot, as on uniform keys; a program that lacks the counter
has nothing to read."""


def read(run):
    kept = run.counters.get("join.skew.kept_rows")
    queries = run.counters.get("queries")
    if kept is None or not queries:
        return None
    probe = run.cell.reference.rows_per_side(run.cell.cfg, run.cell.chips)
    return 100.0 * kept / (probe * queries)
