"""The one traffic generator.  A traffic mix is a data file,
``bench/traffic/<name>.json``; this module reads its parameters and
drives the configuration's cycle of queries with them.  Window statistics
live here too, so that every cell computes its rates and tails alike.
"""
from __future__ import annotations

import json
import math
import os
import time

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")


def load(name: str) -> dict:
    with open(os.path.join(_DIR, name + ".json")) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed" or mix.get("callers") != 1:
        raise ValueError(
            f"traffic {name!r}: this generator drives a closed loop of one "
            f"caller; got loop={mix.get('loop')!r} callers={mix.get('callers')!r}")
    return mix


def drive(mix: dict, issue, cycle: list, seconds: float,
          clock=time.perf_counter, sleep=time.sleep) -> tuple:
    """Issue ``cycle[i % len(cycle)]`` through ``issue(i, query)`` until
    ``seconds`` have passed, each query submitted when the one before it
    has completed (plus the mix's think time).  A query that was submitted
    inside the window is waited for, so the window ends at the last
    completion.  Returns (window start, [record, ...]); ``issue`` returns
    the record, a dict with at least ``rows``, ``ok`` and ``end``, and this
    adds ``due``, the instant the query was submitted."""
    records = []
    t0 = clock()
    i = 0
    while True:
        due = clock()
        if due - t0 >= seconds:
            break
        rec = issue(i, cycle[i % len(cycle)])
        rec["due"] = due
        records.append(rec)
        i += 1
        if mix.get("think_s"):
            sleep(mix["think_s"])
    return t0, records


def rows_per_s(t0: float, records: list) -> float:
    """All the rows of the queries that completed, over all the time from
    the window's start to the last completion."""
    done = [r for r in records if r["ok"]]
    if not done:
        return 0.0
    return sum(r["rows"] for r in done) / (max(r["end"] for r in done) - t0)


def latencies_ms(records: list) -> list:
    """Due time to result on the host, of every query that completed."""
    return [(r["end"] - r["due"]) * 1e3 for r in records if r["ok"]]


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]
