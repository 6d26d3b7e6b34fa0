"""Bytes of the exchanges' send operands in the layout the collective
moves (counter ``shuffle.operand_bytes``: one 128-lane vector a row on a
TPU, whatever the plane's width), per input row of the queries completed.
A program without the counter gives nothing."""


def read(run):
    moved = run.counters.get("shuffle.operand_bytes")
    rows = sum(r["rows"] for r in run.records if r["ok"])
    return moved / rows if moved and rows else None
