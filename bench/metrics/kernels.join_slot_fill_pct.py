"""How full the joins' output slots are: 100 x the rows the joins of the
window gave (counter ``join.out_rows``, the shards' row counts summed)
over the slots they ran in (``join.out_slots``, the capacity a shard times
the shards).  A join's capacity is a step above its fullest shard, and
every later buffer is sized off it, so the rest is rows that sorts, scans
and gathers move for nothing.  A program that lacks the counters has
nothing to read."""


def read(run):
    slots = run.counters.get("join.out_slots")
    if not slots:
        return None
    return 100.0 * run.counters.get("join.out_rows", 0) / slots
