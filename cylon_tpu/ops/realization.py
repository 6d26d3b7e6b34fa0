"""How the local kernels are realized on each platform: one table.

The code chooses from the one thing it can observe, ``precision.on_tpu()``;
no environment variable and no setter changes it.  Code that has to run the
other platform's row (the agreement tests, ``chip_smoke.py``, the dry run)
substitutes ``current`` and drops every traced program round the
substitution (``tests/conftest.py::realize``).
"""
from __future__ import annotations

from typing import NamedTuple

from .. import precision


class Realization(NamedTuple):
    permute: str  # compactions, partitions, inverse permutes, counts
    scan: str     # segments.run_extents' cumsum / cummax / cummin
    segsum: str   # narrow-mode float / min / max segment reductions


#: What every line of PERF_LEDGER.jsonl ran.  XLA:TPU serializes scatters: a
#: 2^26-row sort took 213 ms, a scatter pass about 900 ms (round-4 profile),
#: and a 32-bit lane costs 15-18 ms through a sort, 0.145 s through an index
#: (PERF.md §6, PR 26).  XLA's scans cost 18-45 s of compile each at 2^20
#: rows, ``lax.associative_scan`` 72 s and over 400 s at 2^22; the Pallas
#: kernels about a second at any size (PERF.md §6, PRs 22-25).
ON_TPU = Realization(permute="sort", scan="pallas", segsum="pallas")
#: XLA:CPU and the tier-1 mesh: a scatter is one linear pass there, and the
#: Pallas kernels would run interpreted.
ELSEWHERE = Realization(permute="scatter", scan="xla", segsum="scatter")


def current() -> Realization:
    """The row of the table for the platform this process computes on."""
    return ON_TPU if precision.on_tpu() else ELSEWHERE
