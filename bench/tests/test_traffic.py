"""The closed loop's arithmetic on a fake clock."""
from bench import traffic

MIX = {"loop": "closed", "callers": 1, "think_s": 0}


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def drive(durations, seconds=10.0):
    clock = FakeClock()

    def issue(i, query):
        clock.now += durations[min(i, len(durations) - 1)]
        return {"rows": 1000, "ok": True, "end": clock.now}

    return traffic.drive(MIX, issue, [{}], seconds, clock=clock)


def test_rate_is_all_rows_over_all_time():
    t0, records = drive([2.0])
    assert len(records) == 5
    assert traffic.rows_per_s(t0, records) == 5 * 1000 / 10.0
    assert traffic.percentile(traffic.latencies_ms(records), 0.95) == 2000.0


def test_a_query_submitted_in_the_window_is_waited_for():
    t0, records = drive([3.0])
    assert len(records) == 4  # the fourth was due at 9 s and ended at 12 s
    assert traffic.rows_per_s(t0, records) == 4 * 1000 / 12.0


def test_one_stalled_query_lowers_the_rate_and_raises_the_tail():
    t0, steady = drive([0.5], seconds=10.0)
    t1, stalled = drive([0.5] * 7 + [4.0] + [0.5], seconds=10.0)
    assert traffic.rows_per_s(t1, stalled) < traffic.rows_per_s(t0, steady)
    p95 = traffic.percentile(traffic.latencies_ms(stalled), 0.95)
    assert p95 == 4000.0 > traffic.percentile(
        traffic.latencies_ms(steady), 0.95)
    # a median of per-query rates would not have seen it
    assert traffic.percentile(traffic.latencies_ms(stalled), 0.5) == 500.0


def test_failed_queries_count_for_nothing():
    records = [{"rows": 10, "ok": True, "end": 1.0, "due": 0.0},
               {"rows": 10, "ok": False, "end": 2.0, "due": 1.0}]
    assert traffic.rows_per_s(0.0, records) == 10.0
    assert traffic.latencies_ms(records) == [1000.0]


def test_percentile_is_nearest_rank():
    values = list(range(1, 21))
    assert traffic.percentile(values, 0.95) == 19
    assert traffic.percentile(values, 1.0) == 20
    assert traffic.percentile([7], 0.95) == 7
