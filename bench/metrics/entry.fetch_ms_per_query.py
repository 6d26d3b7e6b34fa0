"""Mean time of the harness's span round the result's to_numpy(), which
starts when the device has finished the query (host clock)."""


def read(run):
    spans = [r["end"] - r["ran"] for r in run.records if r["ok"]]
    return sum(spans) / len(spans) * 1e3 if spans else None
