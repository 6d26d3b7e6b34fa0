"""The four per-layer metrics that read the program's own spans and
counters, on a hand-made run: per completed query of the window; a span
that never opened is a measured 0; a program that records no ``obs.root``
(the parent of the PR that added them) gives nothing and does not raise."""
from types import SimpleNamespace

import pytest

from bench.run import load_reader

NAMES = ("entry.dispatch_ms_per_query", "entry.host_syncs_per_query",
         "entry.fetch_d2h_ms_per_query", "entry.fetch_mb_per_query")

# five queries: 25 s inside the program's root spans, of it 24 s waiting
# in host syncs and 0.9 s in fetches, 0.8 s of those in the copies
SPANS = {"obs.root": (25.0, 20), "host.sync": (24.0, 5),
         "table.fetch": (0.9, 5), "table.fetch.d2h": (0.8, 45),
         "table.distributed_join": (24.05, 5)}
COUNTERS = {"queries": 5, "host.syncs": 5, "table.fetch.bytes": 1.1e9,
            "plan_cache.hit": 15}


def read(name, spans, counters):
    return load_reader(name)(SimpleNamespace(spans=spans, counters=counters))


def test_readers_on_a_hand_made_run():
    got = {n: read(n, SPANS, COUNTERS) for n in NAMES}
    assert got == pytest.approx({
        "entry.dispatch_ms_per_query": 20.0,   # (25 - 24 - 0.9) s / 5
        "entry.host_syncs_per_query": 1.0,
        "entry.fetch_d2h_ms_per_query": 160.0,
        "entry.fetch_mb_per_query": 220.0})


def test_a_span_that_never_opened_is_a_measured_zero():
    """A cell that never fetches or never syncs still reports."""
    spans = {"obs.root": (1.0, 5)}
    counters = {"queries": 5}
    assert read("entry.dispatch_ms_per_query", spans, counters) == \
        pytest.approx(200.0)
    for name in NAMES[1:]:
        assert read(name, spans, counters) == 0


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_gives_nothing(name):
    old = {k: v for k, v in SPANS.items() if k == "table.distributed_join"}
    assert read(name, old, {"queries": 5, "plan_cache.hit": 15}) is None
    assert read(name, {}, {"queries": 5}) is None
    # no completed query: nothing to divide by
    assert read(name, SPANS, {**COUNTERS, "queries": 0}) is None
