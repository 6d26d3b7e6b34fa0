"""How evenly the joins of the window spread over the chips: 100 x the
fullest shard's join rows (counter ``join.shard_rows_max``, summed over the
joins) x the cell's chips over the joins' rows (``join.out_rows``).  100 is
a perfectly even join; the fullest shard sets every shard's capacity, so
the rest is rows the other shards' kernels move for nothing.  A program
that lacks the counter has nothing to read."""


def read(run):
    fullest = run.counters.get("join.shard_rows_max")
    rows = run.counters.get("join.out_rows")
    if fullest is None or not rows:
        return None
    return 100.0 * fullest * run.cell.chips / rows
