"""95th percentile, over all queries of the window, of the time from when
a query was due to its result on the host (host clock)."""
from bench import traffic


def read(run):
    values = traffic.latencies_ms(run.records)
    return traffic.percentile(values, 0.95) if values else None
