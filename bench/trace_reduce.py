"""From a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a recorded fixture
without a chip: ``load_events`` reads an ``.xplane.pb`` into plain lists
(device operations per chip, and the harness's own ``bench.*`` host
annotations, all on the trace's clock, in seconds), and ``reduce`` turns
those lists into busy and idle time, time by category of operation, and
the idle gaps labelled by what the harness was doing.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
COLLECTIVES = ("ragged-all-to-all", "all-to-all", "all-gather", "all-reduce",
               "collective-permute", "reduce-scatter", "collective-broadcast")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_DETAIL = re.compile(r'kind=(\w+)|custom_call_target="([^"]+)"')


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(hlo_text: str, module: str) -> str:
    """The profiler names a device operation by its whole HLO text.  Keep
    what identifies it: ``<module>/<%name> <opcode>[:<fusion kind or
    custom-call target>] <result type>``."""
    name, _, rest = hlo_text.partition(" = ")
    found = _OPCODE.search(" " + rest)
    opcode = found.group(1) if found else "?"
    detail = _DETAIL.search(rest) if opcode in ("fusion", "custom-call") \
        else None
    if detail:
        opcode += ":" + (detail.group(1) or detail.group(2))
    result = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{module}/{name} {opcode} {'tuple' if result[:1] == '(' else result}"


def load_events(xplane_path: str) -> dict:
    """{"devices": {plane name: [[operation, start_s, dur_s], ...]},
    "host": [[annotation name, start_s, dur_s], ...]}: the operations of
    each chip's "XLA Ops" line under their short names, each with the jitted
    program ("XLA Modules" line) that was running when it started."""
    import bisect

    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 ev.name.split("(")[0])
                for ev in lines[MODULES_LINE].events
            ) if MODULES_LINE in lines else []
            starts = [m[0] for m in modules]
            ops = []
            for ev in lines[OPS_LINE].events:
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                module = (modules[i][2] if i >= 0
                          and ev.start_ns < modules[i][1] else "?")
                ops.append([short_name(ev.name, module),
                            ev.start_ns * 1e-9, ev.duration_ns * 1e-9])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9]
                    for ev in line.events
                    if ev.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def category(op: str) -> str:
    """collective, sort, pallas or other, from a short name's opcode."""
    opcode, _, detail = (op.split(" ") + ["?"])[1].partition(":")
    if opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES:
        return "collective"
    if opcode == "sort":
        return "sort"
    if opcode == "custom-call" and detail == "tpu_custom_call":
        return "pallas"
    return "other"


def union(intervals: list) -> list:
    """Sorted, disjoint [start, end] intervals covering the same time."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events: list) -> dict:
    """{op name: seconds in which it was the innermost operation running}.
    A while loop's event spans its body's operations on the same line; each
    instant is given to the operation that started last."""
    out = {}
    stack = []  # [name, end, resumed_at]

    def close(until: float):
        name, end, since = stack.pop()
        out[name] = out.get(name, 0.0) + max(0.0, min(end, until) - since)
        if stack:
            stack[-1][2] = max(stack[-1][2], min(end, until))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack[-1][1])
        if stack:
            top = stack[-1]
            out[top[0]] = out.get(top[0], 0.0) + max(0.0, start - top[2])
            top[2] = start
        stack.append([name, start + dur, start])
    while stack:
        close(stack[-1][1])
    return out


def _overlap(intervals: list, lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(intervals, lo, hi))


def reduce(events: dict) -> dict:
    """The traced window runs from the first ``bench.query`` annotation to
    the end of the last ``bench.fetch``; everything is taken inside it."""
    host = events["host"]
    queries = [[s, s + d] for n, s, d in host if n == HOST_PREFIX + "query"]
    fetches = [[s, s + d] for n, s, d in host if n == HOST_PREFIX + "fetch"]
    if not queries or not events["devices"]:
        return {}
    lo = min(s for s, _ in queries)
    hi = max(e for _, e in queries + fetches)
    devices = {}
    for plane, evs in sorted(events["devices"].items()):
        evs = [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
               for n, s, d in evs if min(s + d, hi) > max(s, lo)]
        busy = union([[s, s + d] for _, s, d in evs])
        ops = self_times(evs)
        by_cat = {}
        for name, secs in ops.items():
            cat = category(name)
            by_cat[cat] = by_cat.get(cat, 0.0) + secs
        devices[plane] = {"busy_s": sum(e - s for s, e in busy),
                          "busy": busy, "ops": ops, "categories": by_cat}
    worst = min(devices, key=lambda p: devices[p]["busy_s"])
    # idle gaps of the idlest chip, each split over what the harness was in
    in_fetch = union(fetches)
    in_query = union(queries)
    gaps, by_label = [], {"query": 0.0, "fetch": 0.0, "between": 0.0}
    edge = lo
    for s, e in devices[worst]["busy"] + [[hi, hi]]:
        if s > edge:
            fetch = _overlap(in_fetch, edge, s)
            query = _overlap(in_query, edge, s) - fetch
            parts = {"query": query, "fetch": fetch,
                     "between": (s - edge) - query - fetch}
            for label, secs in parts.items():
                by_label[label] += secs
            gaps.append([max(parts, key=parts.get), s - edge])
        edge = max(edge, e)
    n = len(devices)
    ops_mean, cats_mean, modules_mean = {}, {}, {}
    for d in devices.values():
        for name, secs in d["ops"].items():
            ops_mean[name] = ops_mean.get(name, 0.0) + secs / n
            module = name.split("/", 1)[0]
            modules_mean[module] = modules_mean.get(module, 0.0) + secs / n
        for name, secs in d["categories"].items():
            cats_mean[name] = cats_mean.get(name, 0.0) + secs / n
    return {
        "window_s": hi - lo,
        "queries": len(queries),
        "query_s": sum(e - s for s, e in queries),
        "chips": n,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
        "busy_s_min": devices[worst]["busy_s"],
        "categories_s": cats_mean,
        "modules_s": modules_mean,
        "ops_s": ops_mean,
        "idle_by_label_s": by_label,
        "gaps": sorted(gaps, key=lambda g: -g[1]),
    }


def breakdown(reduced: dict, top: int = 10, programs: int = 4) -> dict:
    """The contract's ``breakdown``: the jitted programs that took most
    device time (``<program>/*``) and then the single operations that did;
    the idle time by what the harness was doing, and then the longest
    single gaps."""
    def most(table: dict, n: int) -> list:
        return sorted(table.items(), key=lambda kv: -kv[1])[:n]

    ops = [[name + "/*", secs]
           for name, secs in most(reduced["modules_s"], programs)]
    ops += [[name, secs] for name, secs in
            most(reduced["ops_s"], top - len(ops))]
    idle = [["all." + k, v] for k, v in most(reduced["idle_by_label_s"], 3)]
    idle += [["gap." + label, secs]
             for label, secs in reduced["gaps"][:top - len(idle)]]
    return {"device_ops": ops, "idle_gaps": idle}
