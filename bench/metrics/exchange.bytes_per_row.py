"""Bytes the program counted as sent through exchanges in the window
(``shuffle.bytes_sent``), per input row of the queries completed."""


def read(run):
    sent = run.counters.get("shuffle.bytes_sent")
    rows = sum(r["rows"] for r in run.records if r["ok"])
    return sent / rows if sent and rows else None
