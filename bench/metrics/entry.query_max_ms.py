"""The slowest query of the window, due time to result on the host."""
from bench import traffic


def read(run):
    values = traffic.latencies_ms(run.records)
    return max(values) if values else None
