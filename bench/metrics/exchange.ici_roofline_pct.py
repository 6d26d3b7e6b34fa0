"""The least time the exchange could take for a query -- the bytes that
have to change chips, sent by each chip at its peak interconnect rate --
over the device's time in collective operations per query.  Defined on the
query: of ``work_bytes`` (every input column once, the result once) the
share ``1 - 1/chips`` leaves its chip, because keys are uniform and a row
stays where it is with probability ``1/chips``.  It reads the same
whatever layout the collective moves."""


def read(run):
    t = run.trace
    secs = t.get("categories_s", {}).get("collective") if t else None
    if not secs or not run.work_bytes or t["chips"] < 2:
        return None
    work = sum(run.work_bytes) / len(run.work_bytes)
    leaves_a_chip = work * (1.0 - 1.0 / t["chips"]) / t["chips"]
    least_s = leaves_a_chip / (run.peaks["ici_bits_per_s"] / 8.0)
    return 100.0 * least_s / (secs / t["queries"])
