"""Rounds the exchanges of the window went in (counter ``shuffle.rounds``:
one for an exchange whose shard is under the collective's operand limit),
per completed query.  A program without the counter gives nothing."""


def read(run):
    rounds = run.counters.get("shuffle.rounds")
    queries = run.counters.get("queries")
    return rounds / queries if rounds and queries else None
