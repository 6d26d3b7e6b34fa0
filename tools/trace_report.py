"""Summarize a cylon_tpu.obs trace export: top-K self-time + collectives.

Loads a Chrome-trace JSON written by ``cylon_tpu.obs.export`` (or merged
by ``tools/trace_merge.py``) and prints

- a top-K table by SELF time (a span's duration minus its children's, so
  a fat parent that merely wraps a fat child doesn't dominate the table),
- the instant-event tally (retries, injected faults, OOM refinements),
- per-collective skew rows when the trace carries cross-rank
  ``collective.arrive`` instants (a merged elastic trace),
- per-tenant SLO latency rows (queue-wait vs run split) from the serve
  histograms in the metrics artifact,
- when the sibling metrics artifact exists (``<name>.metrics.rN.json``
  next to the trace, or passed explicitly), the collective/bytes summary
  — launches, exchanges, bytes sent, plan-cache traffic.

A trace whose buffer DROPPED events gets a loud stderr warning: totals
and skew from a truncated buffer are misleading, and silently reporting
them would launder bad numbers into good-looking tables.

``--json`` emits the whole report as one machine-readable object
(totals, skew table, SLO rows) so CI and the battery can assert on
content instead of grepping human text.

``--device`` reads a ``jax.profiler`` trace instead (the directory the
profiler wrote, or its ``.xplane.pb``): device self seconds by kernel
stage (``cylon_tpu.obs.STAGES``, from each operation's ``op_name``;
operations under no stage as ``<program>/unscoped``), the idlest chip's
idle seconds by the innermost program span open on the host, and the
program's spans found on the profiler's clock.

Usage:
    python tools/trace_report.py TRACE.json [METRICS.json] [--top K]
                                 [--json]
    python tools/trace_report.py --device PROFILE_DIR [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple


def load_trace(path: str) -> Dict[str, object]:
    """Load and validate a Chrome-trace export (the same schema contract
    as ``cylon_tpu.obs.export.load_trace``, duplicated here so the
    reporter stays a pure-JSON tool — no jax, no package import)."""
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    evs = doc.get("traceEvents")
    if not isinstance(evs, list):
        raise ValueError(f"{path}: not a Chrome-trace export "
                         f"(missing traceEvents list)")
    for ev in evs:
        for k in ("name", "ph", "ts", "pid", "tid"):
            if k not in ev:
                raise ValueError(f"{path}: event missing {k!r}: {ev}")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"{path}: complete event missing dur: {ev}")
    return doc


def load_metrics(path: str) -> Dict[str, object]:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def self_times(events: List[dict]) -> Dict[str, Tuple[int, float, float]]:
    """{name: (count, total_us, self_us)} over the "X" events.

    Self time subtracts each span's direct children, found by interval
    containment per (pid, tid) with a stack sweep over start-ordered
    events — the standard flame-graph attribution."""
    total: Dict[str, float] = defaultdict(float)
    self_t: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    by_track: Dict[tuple, List[list]] = defaultdict(list)
    for e in events:
        if e.get("ph") == "X":
            # local [name, ts, dur, child_acc] records — never mutate the
            # caller's dicts, so repeat calls on one loaded trace agree
            by_track[(e.get("pid"), e.get("tid"))].append(
                [e["name"], e["ts"], e["dur"], 0.0])
    for track in by_track.values():
        track.sort(key=lambda r: (r[1], -r[2]))
        stack: List[list] = []  # enclosing spans, child time accumulating
        for rec in track:
            name, ts, dur, _ = rec
            while stack and ts >= stack[-1][1] + stack[-1][2]:
                done = stack.pop()
                self_t[done[0]] += done[2] - done[3]
            if stack:
                stack[-1][3] += dur
            total[name] += dur
            count[name] += 1
            stack.append(rec)
        while stack:
            done = stack.pop()
            self_t[done[0]] += done[2] - done[3]
    return {n: (count[n], total[n], self_t[n]) for n in total}


def tenant_attribution(events: List[dict]) -> Dict[str, Tuple[int, float]]:
    """{tenant: (request count, total_us)} over ``serve.request`` spans —
    per-tenant attribution of where the mesh's serving time went."""
    out: Dict[str, Tuple[int, float]] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("name") != "serve.request":
            continue
        tenant = str(e.get("args", {}).get("tenant", "?"))
        n, us = out.get(tenant, (0, 0.0))
        out[tenant] = (n + 1, us + e.get("dur", 0))
    return out


_sibling_cache: Dict[str, object] = {}


def _sibling_tool(name: str):
    """A sibling tool module, loaded by file path — ONE implementation
    of the shared math (skew attribution in trace_merge.py, the
    critical-path walk in critical_path.py) without any tool gaining a
    package import (all stay pure stdlib)."""
    mod = _sibling_cache.get(name)
    if mod is None:
        import importlib.util

        p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         f"{name}.py")
        spec = importlib.util.spec_from_file_location(f"_{name}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _sibling_cache[name] = mod
    return mod


def _merge_tool():
    return _sibling_tool("trace_merge")


def _cp_tool():
    return _sibling_tool("critical_path")


def collective_skew(events: List[dict]) -> List[dict]:
    """Per-collective skew rows from ``collective.arrive`` /
    ``collective.depart`` instants grouped by (collective, epoch, seq) —
    meaningful on a MERGED trace where the instants come from several
    ranks on one aligned clock.  Delegates to trace_merge.py so the
    attribution math has exactly one implementation."""
    return _merge_tool().collective_skew(events)


def slo_rows(metrics_doc: dict) -> Dict[str, dict]:
    """Per-tenant SLO latency rows from the serve histograms
    (``serve.queue_wait_ms[<tenant>]`` / ``serve.run_ms[<tenant>]``)."""
    out: Dict[str, dict] = {}
    for key, h in (metrics_doc.get("histograms") or {}).items():
        if not key.startswith("serve.") or "[" not in key:
            continue
        kind, tenant = key[len("serve."):].split("[", 1)
        n = int(h.get("count", 0))
        out.setdefault(tenant.rstrip("]"), {})[kind] = {
            "count": n,
            "mean_ms": (float(h.get("sum", 0.0)) / n) if n else None,
            "min_ms": h.get("min"), "max_ms": h.get("max")}
    return out


def _dropped_warning(where: str, dropped: int) -> None:
    if dropped > 0:
        print(f"trace_report: WARNING: {where} DROPPED {dropped} events "
              f"(CYLON_TPU_TRACE_BUFFER_CAP too small) — self-time and "
              f"skew numbers from a truncated buffer are misleading",
              file=sys.stderr)


def load_plan_profile(path: str) -> dict:
    """Load and validate a plan-profile artifact (the JSON
    ``plan/profile.py`` exports; schema duplicated here so the reporter
    stays a pure-JSON tool)."""
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    if doc.get("kind") != "cylon_tpu.plan_profile":
        raise ValueError(f"{path}: not a plan profile "
                         f"(kind={doc.get('kind')!r})")
    if not isinstance(doc.get("nodes"), list):
        raise ValueError(f"{path}: nodes is not a list")
    return doc


def print_plan_profile(doc: dict) -> None:
    """Per-plan-node EXPLAIN ANALYZE table from a profile artifact:
    the tree (indented by depth), estimate→actual rows, self time,
    exchange bytes, and shard skew with the slowest shard named."""
    print(f"\nplan profile: world={doc.get('world')} "
          f"wall={doc.get('wall_ms', 0):.1f}ms "
          f"cache_hit={doc.get('plan_cache_hit')} "
          f"estimates={'catalog' if doc.get('had_estimates') else '-'}")
    print(f"  {'node':44s} {'est rows':>9s} {'rows':>9s} {'self ms':>9s} "
          f"{'bytes sent':>11s} {'skew':>8s}")
    for n in sorted(doc.get("nodes") or [], key=lambda n: n.get("nid", 0)):
        label = ("  " * int(n.get("depth", 0))
                 + str(n.get("desc") or n.get("kind") or "?"))[:44]
        est = n.get("est_rows")
        bytes_sent = int((n.get("metrics") or {}).get(
            "shuffle.bytes_sent", 0))
        skew = (f"{n['skew']:.2f}@r{n.get('slowest_shard')}"
                if n.get("skew") is not None else "-")
        print(f"  {label:44s} {'-' if est is None else est:>9} "
              f"{n.get('rows', 0):>9} {n.get('self_ms', 0):>9.2f} "
              f"{bytes_sent:>11d} {skew:>8s}")


def report_dict(trace_path: str, metrics_path: Optional[str],
                top: int, plan_path: Optional[str] = None,
                critical_path: bool = False,
                trace_id: Optional[str] = None) -> dict:
    """The whole report as one machine-readable object (``--json``)."""
    doc = load_trace(trace_path)
    events = doc["traceEvents"]
    other = doc.get("otherData", {})
    st = self_times(events)
    instants: Dict[str, int] = defaultdict(int)
    for e in events:
        if e.get("ph") == "i":
            instants[e["name"]] += 1
    metrics_path = _sibling_metrics(trace_path, metrics_path)
    m = load_metrics(metrics_path) if metrics_path else {}
    cp = _cp_tool().critical_path(events, trace_id) \
        if critical_path else None
    return {
        **({"plan": load_plan_profile(plan_path)} if plan_path else {}),
        **({"critical_path": cp} if critical_path else {}),
        "trace": trace_path,
        "rank": other.get("rank"),
        "run_id": other.get("run_id"),
        "events": len(events),
        "dropped_events": int(other.get("dropped_events", 0) or 0),
        "totals": {
            "spans": sum(n for n, _, _ in st.values()),
            "self_ms": round(sum(s for _, _, s in st.values()) / 1e3, 6),
        },
        "self_times": [
            {"span": name, "count": n, "total_ms": round(tot / 1e3, 6),
             "self_ms": round(self_us / 1e3, 6)}
            for name, (n, tot, self_us)
            in sorted(st.items(), key=lambda kv: -kv[1][2])[:top]],
        "instants": dict(sorted(instants.items())),
        "tenants": {t: {"requests": n, "total_ms": round(us / 1e3, 6)}
                    for t, (n, us)
                    in sorted(tenant_attribution(events).items())},
        "skew": collective_skew(events),
        "slo": slo_rows(m),
        "metrics": metrics_path,
        "counters": m.get("counters", {}),
        "gauges": m.get("gauges", {}),
    }


def _sibling_metrics(trace_path: str,
                     metrics_path: Optional[str]) -> Optional[str]:
    """Resolve the metrics artifact beside a trace (explicit path wins)."""
    if metrics_path is not None:
        return metrics_path if os.path.exists(metrics_path) else None
    d, base = os.path.split(trace_path)
    head, _, rest = base.partition(".")
    cands = [
        # export_all naming: prefix.rN.json -> prefix.metrics.rN.json
        os.path.join(d, re.sub(r"\.r(\d+)\.json$", r".metrics.r\1.json",
                               base)),
        # run-id naming: prefix.<run>.rN.json -> prefix.metrics.<run>.rN.json
        os.path.join(d, f"{head}.metrics.{rest}") if rest else "",
        # plain export naming: trace.rN.json -> metrics.rN.json
        os.path.join(d, base.replace("trace", "metrics", 1)),
    ]
    for cand in cands:
        if cand and cand != trace_path and os.path.exists(cand):
            return cand
    return None


def print_report(trace_path: str, metrics_path: "str | None",
                 top: int, doc: "Dict[str, object] | None" = None) -> None:
    if doc is None:
        doc = load_trace(trace_path)
    events = doc["traceEvents"]
    other = doc.get("otherData", {})
    st = self_times(events)
    grand_self = sum(s for _, _, s in st.values()) or 1.0
    dropped = int(other.get("dropped_events", 0) or 0)
    _dropped_warning(trace_path, dropped)
    print(f"trace: {trace_path}  rank={other.get('rank', '?')}  "
          f"events={len(events)}  dropped={dropped}")
    print(f"\ntop {top} by self time:")
    print(f"{'span':34s} {'count':>7s} {'total ms':>10s} {'self ms':>10s} "
          f"{'self %':>7s}")
    ranked = sorted(st.items(), key=lambda kv: -kv[1][2])[:top]
    for name, (n, tot, self_us) in ranked:
        print(f"{name:34s} {n:7d} {tot / 1e3:10.3f} {self_us / 1e3:10.3f} "
              f"{100 * self_us / grand_self:6.1f}%")

    instants: Dict[str, int] = defaultdict(int)
    for e in events:
        if e.get("ph") == "i":
            instants[e["name"]] += 1
    if instants:
        print("\ninstant events:")
        for name in sorted(instants):
            print(f"  {name:32s} {instants[name]:7d}")

    tenants = tenant_attribution(events)
    if tenants:
        print("\nper-tenant serving attribution:")
        print(f"  {'tenant':24s} {'requests':>8s} {'total ms':>10s}")
        for t in sorted(tenants, key=lambda t: -tenants[t][1]):
            n, us = tenants[t]
            print(f"  {t:24s} {n:8d} {us / 1e3:10.3f}")

    skew = collective_skew(events)
    if skew:
        print("\nper-collective skew (slowest-rank attribution; "
              "meaningful on a merged, clock-aligned trace):")
        print(f"  {'collective':40s} {'epoch':>5s} {'ranks':>5s} "
              f"{'skew ms':>9s}  slowest")
        for r in skew:
            print(f"  {r['collective'][:40]:40s} {str(r['epoch']):>5s} "
                  f"{len(r['ranks']):>5d} {r['skew_us'] / 1e3:9.3f}  "
                  f"r{r['slowest_rank']}")

    metrics_path = _sibling_metrics(trace_path, metrics_path)
    if metrics_path and os.path.exists(metrics_path):
        m = load_metrics(metrics_path)
        c = m.get("counters", {})
        slo = slo_rows(m)
        if slo:
            print("\nper-tenant SLO latency (queue-wait vs run):")
            print(f"  {'tenant':20s} {'phase':>12s} {'count':>6s} "
                  f"{'mean ms':>9s} {'max ms':>9s}")
            for t, row in sorted(slo.items()):
                for kind in ("queue_wait_ms", "run_ms"):
                    h = row.get(kind)
                    if not h or not h["count"]:
                        continue
                    print(f"  {t:20s} {kind[:-3]:>12s} {h['count']:6d} "
                          f"{h['mean_ms']:9.2f} {h['max_ms']:9.2f}")
        g = m.get("gauges", {})
        print(f"\nmetrics: {metrics_path}")
        print(f"  shuffle exchanges          {c.get('shuffle.exchanges', 0):>12}")
        print(f"  collective launches        "
              f"{c.get('shuffle.collective_launches', 0):>12}")
        print(f"  counts gathers             "
              f"{c.get('shuffle.counts_gathers', 0):>12}")
        print(f"  bytes sent                 "
              f"{c.get('shuffle.bytes_sent', 0):>12}")
        if "shuffle.bytes_saved" in c or "shuffle.compress_ratio" in g:
            # the PR-10 compression win belongs in the standard report:
            # bytes that never traveled, and the last exchange's ratio
            ratio = g.get("shuffle.compress_ratio")
            print(f"  bytes saved (compression)  "
                  f"{int(c.get('shuffle.bytes_saved', 0)):>12}"
                  + (f"  (last ratio {float(ratio):.2f}x)"
                     if ratio else ""))
        print(f"  plan cache hit/miss        "
              f"{c.get('plan_cache.hit', 0)}/{c.get('plan_cache.miss', 0)}")
        print(f"  retries / oom refinements  "
              f"{c.get('retry.attempts', 0)}/{c.get('oom.refinements', 0)}")
        if any(k.startswith(("durable.", "deadline.", "quarantine."))
               for k in c):
            # durable-execution summary: how much of the run was served
            # from the journal vs re-executed, and why
            print(f"  journaled / skipped passes "
                  f"{int(c.get('durable.passes_journaled', 0))}/"
                  f"{int(c.get('durable.passes_skipped', 0))}")
            print(f"  spill bytes / rejected     "
                  f"{int(c.get('durable.spill_bytes', 0))}/"
                  f"{int(c.get('durable.spills_rejected', 0))}")
            print(f"  deadlines / quarantined    "
                  f"{int(c.get('deadline.fired', 0))}/"
                  f"{int(c.get('quarantine.parts', 0))}")
        if any(k.startswith("serve.") for k in c):
            # serving summary: admission vs shed vs cache traffic — the
            # overload story in four lines
            print(f"  serve admitted / shed      "
                  f"{int(c.get('serve.admitted', 0))}/"
                  f"{int(c.get('serve.shed', 0))}")
            print(f"  serve completed / failed   "
                  f"{int(c.get('serve.completed', 0))}/"
                  f"{int(c.get('serve.failed', 0))}")
            evicts = int(c.get("serve.cache_evictions", 0)
                         or c.get("durable.gc_runs_evicted", 0))
            print(f"  serve cache hits / evicts  "
                  f"{int(c.get('serve.cache_hit', 0))}/{evicts}")
            print(f"  serve cancelled / tenants quarantined "
                  f"{int(c.get('serve.cancelled', 0))}/"
                  f"{int(c.get('serve.tenants_quarantined', 0))}")
        if any(k.startswith("stream.") for k in c):
            # streaming-ingest summary (PR 19): appended volume vs what
            # refreshes actually touched — rows_delta tracking batch
            # rows IS the incrementality evidence
            print(f"  stream batches / rows      "
                  f"{int(c.get('stream.batches_appended', 0))}/"
                  f"{int(c.get('stream.rows_appended', 0))}")
            print(f"  refreshes / cached         "
                  f"{int(c.get('stream.refreshes', 0))}/"
                  f"{int(c.get('stream.refresh_cached', 0))}")
            print(f"  delta rows folded          "
                  f"{int(c.get('stream.rows_delta', 0)):>12}"
                  + (f"  (state regrown x"
                     f"{int(c.get('stream.state_regrown', 0))})"
                     if c.get("stream.state_regrown") else ""))
        g = m.get("gauges", {})
        if "hbm.live_bytes" in g:
            print(f"  hbm watermark bytes        "
                  f"{int(g['hbm.live_bytes']):>12}")
        if "elastic.epoch" in g or any(k.startswith("elastic.")
                                       for k in c):
            # elastic-membership summary: how many times the gang shrank
            # and how often this rank re-derived its slice
            print(f"  membership epoch           "
                  f"{int(g.get('elastic.epoch', 0)):>12}")
            print(f"  ranks lost / resumes       "
                  f"{int(c.get('elastic.rank_lost', 0))}/"
                  f"{int(c.get('elastic.resume', 0))}")


# ---------------------------------------------------------------------------
# --device: a jax.profiler trace, by kernel stage and by program span
# ---------------------------------------------------------------------------

def _pb_fields(buf):
    """(field number, value) pairs of one protobuf message: varints as
    int, length-delimited fields as a memoryview, fixed ones skipped.
    The profiler's xplane file needs no more, and ``ProfileData`` does
    not surface the statistics of the event METADATA, where each device
    operation's ``op_name`` (stat ``tf_op``) lives."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return v

    while i < n:
        key = varint()
        num, wire = key >> 3, key & 7
        if wire == 0:
            yield num, varint()
        elif wire == 2:
            size = varint()
            yield num, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _pb_text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def load_xplane(path: str) -> dict:
    """{"devices": {plane: [[program, op_name, start_s, dur_s], ...]},
    "host": [[name, start_s, dur_s], ...]} on the trace's clock: every
    operation of each chip's "XLA Ops" line with the jitted program that
    was running ("XLA Modules") and its ``tf_op``, and every host event."""
    import bisect

    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    devices, host = {}, []
    for num, plane in _pb_fields(space):
        if num != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for f, v in _pb_fields(plane):
            if f == 2:
                name = _pb_text(v)
            elif f == 3:
                lines.append(v)
            elif f in (4, 5):  # map<int64, X{Event,Stat}Metadata>
                entry = dict(_pb_fields(v))
                (event_meta if f == 4 else stat_names)[entry[1]] = entry[2]
        on_device = name.startswith("/device:TPU:")
        if not (on_device or name.startswith("/host:")):
            continue
        stat_names = {k: _pb_text(dict(_pb_fields(v)).get(2, b""))
                      for k, v in stat_names.items()}
        names = {}  # metadata id -> (event name, tf_op)
        for mid, meta in event_meta.items():
            ev_name, tf_op = "", ""
            for f, v in _pb_fields(meta):
                if f == 2:
                    ev_name = _pb_text(v)
                elif f == 5:
                    stat = dict(_pb_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = _pb_text(stat.get(5, b"")).rstrip(":")
            names[mid] = (ev_name, tf_op)
        by_line = {}
        for line in lines:
            line_name, t0_ns, events = "", 0, []
            for f, v in _pb_fields(line):
                if f == 2:
                    line_name = _pb_text(v)
                elif f == 3:
                    t0_ns = v
                elif f == 4:
                    ev = dict(_pb_fields(v))
                    events.append((ev[1], ev.get(2, 0), ev.get(3, 0)))
            by_line.setdefault(line_name, []).extend(
                (names[mid], t0_ns * 1e-9 + off * 1e-12, dur * 1e-12)
                for mid, off, dur in events)
        if not on_device:
            host.extend([n[0], s, d] for evs in by_line.values()
                        for n, s, d in evs)
            continue
        modules = sorted((s, s + d, n[0].split("(")[0])
                         for n, s, d in by_line.get("XLA Modules", []))
        starts = [m[0] for m in modules]
        ops = []
        for (_, tf_op), s, d in by_line.get("XLA Ops", []):
            i = bisect.bisect_right(starts, s) - 1
            program = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
            ops.append([program, tf_op, s, d])
        devices[name] = ops
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


_PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")


def is_program_span(name: str) -> bool:
    """The program's spans are dotted lower-case names (``join.gather``,
    ``table.fetch.d2h``); the runtime's own host events never are.
    ``bench.*`` is the harness's."""
    return bool(_PROGRAM_SPAN.match(name))


def device_report(path: str) -> dict:
    """The ``--device`` report as one object.  Device seconds are self
    times (each instant goes to the innermost operation), averaged over
    the chips; idle seconds are the idlest chip's, each instant given to
    the span that opened last among those open on the host."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.trace_reduce import find_xplane, self_times as op_self, union
    from cylon_tpu.obs.spans import STAGES

    if os.path.isdir(path):
        path = find_xplane(path)
    trace = load_xplane(path)
    if not trace["devices"]:
        raise ValueError(f"{path}: no TPU device plane with an XLA Ops line")
    spans = [e for e in trace["host"] if is_program_span(e[0])]

    def label(program: str, tf_op: str) -> str:
        stages = [c for c in tf_op.split("/") if c in STAGES]
        return "/".join(stages) if stages else f"{program}/unscoped"

    stages_s, busy = {}, {}
    n = len(trace["devices"])
    for plane, ops in trace["devices"].items():
        for name, secs in op_self(
                [[label(p, t), s, d] for p, t, s, d in ops]).items():
            stages_s[name] = stages_s.get(name, 0.0) + secs / n
        busy[plane] = union([[s, s + d] for _, _, s, d in ops])
    busy_s = {p: sum(e - s for s, e in b) for p, b in busy.items()}
    idlest = min(busy_s, key=busy_s.get)
    every = [iv for b in busy.values() for iv in b] + \
        [[s, s + d] for _, s, d in spans]
    lo, hi = min(s for s, _ in every), max(e for _, e in every)
    idle_s = {}
    edge = lo
    for s, e in busy[idlest] + [[hi, hi]]:
        if s > edge:
            for name, secs in _by_innermost_span(spans, edge, s).items():
                idle_s[name] = idle_s.get(name, 0.0) + secs
        edge = max(edge, e)
    host_spans = {}
    for name, _, dur in spans:
        count, secs = host_spans.get(name, (0, 0.0))
        host_spans[name] = (count + 1, secs + dur)
    return {"trace": path, "chips": n, "window_s": hi - lo,
            "busy_s": sum(busy_s.values()) / n,
            "idlest_chip": idlest, "idle_s_total": hi - lo - busy_s[idlest],
            "stages_s": dict(sorted(stages_s.items(), key=lambda kv: -kv[1])),
            "idle_s": dict(sorted(idle_s.items(), key=lambda kv: -kv[1])),
            "spans": {k: list(v) for k, v in sorted(host_spans.items())}}


def _by_innermost_span(spans: List[list], lo: float, hi: float) -> dict:
    """{span name: seconds of [lo, hi] in which it was the span that
    opened last among those open}; "-" where none was."""
    open_here = [(s, s + d, name) for name, s, d in spans
                 if s < hi and s + d > lo]
    cuts = sorted({lo, hi, *(t for s, e, _ in open_here for t in (s, e)
                             if lo < t < hi)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        inner = max((sp for sp in open_here if sp[0] <= a and sp[1] >= b),
                    default=(0, 0, "-"))
        out[inner[2]] = out.get(inner[2], 0.0) + (b - a)
    return out


def print_device_report(rep: dict) -> None:
    print(f"device trace: {rep['trace']}  chips={rep['chips']}  "
          f"window {rep['window_s']:.6f} s  busy {rep['busy_s']:.6f} s")
    print(f"\ndevice self seconds by stage:\n{'stage':52s} {'seconds':>12s} "
          f"{'busy %':>7s}")
    busy = rep["busy_s"] or 1.0
    for name, secs in rep["stages_s"].items():
        print(f"{name:52s} {secs:12.6f} {100 * secs / busy:6.2f}%")
    unscoped = sum(v for k, v in rep["stages_s"].items()
                   if k.endswith("/unscoped"))
    print(f"{'(all unscoped)':52s} {unscoped:12.6f} "
          f"{100 * unscoped / busy:6.2f}%")
    print(f"\nidle seconds of {rep['idlest_chip']} by innermost program "
          f"span ({rep['idle_s_total']:.6f} s idle):")
    for name, secs in rep["idle_s"].items():
        print(f"{name:52s} {secs:12.6f}")
    print(f"\nprogram spans on the profiler's clock:\n{'span':52s} "
          f"{'count':>7s} {'seconds':>12s}")
    for name, (count, secs) in rep["spans"].items():
        print(f"{name:52s} {count:7d} {secs:12.6f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="trace_report",
        description="top-K self-time + collective/bytes summary of a "
                    "cylon_tpu.obs trace export")
    ap.add_argument("trace", help="trace JSON written by obs.export (with "
                                  "--device: a jax.profiler directory or "
                                  ".xplane.pb)")
    ap.add_argument("metrics", nargs="?", default=None,
                    help="metrics JSON (default: sibling of the trace)")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout (totals, "
                         "skew table, per-tenant SLO rows)")
    ap.add_argument("--plan", default=None, metavar="PROFILE.json",
                    help="also summarize a plan-profile artifact "
                         "(plan_profile.rN.json from a profiled run / "
                         "EXPLAIN ANALYZE): per-node estimate->actual "
                         "rows, self time, exchange bytes, shard skew")
    ap.add_argument("--critical-path", action="store_true",
                    help="also walk the causal critical path of the "
                         "traced request (tools/critical_path.py): path "
                         "segments + wait/compute/transfer decomposition")
    ap.add_argument("--trace-id", default=None,
                    help="request trace to analyze with --critical-path "
                         "(default: the serve.request root)")
    ap.add_argument("--device", action="store_true",
                    help="TRACE is a jax.profiler trace: device seconds by "
                         "kernel stage, idle seconds by program span")
    args = ap.parse_args(argv)
    if args.device:
        rep = device_report(args.trace)
        if args.json:
            json.dump(rep, sys.stdout, indent=1)
            print()
        else:
            print_device_report(rep)
        return 0
    if args.json:
        rep = report_dict(args.trace, args.metrics, args.top, args.plan,
                          critical_path=args.critical_path,
                          trace_id=args.trace_id)
        _dropped_warning(args.trace, rep["dropped_events"])
        json.dump(rep, sys.stdout, indent=1, sort_keys=True)
        print()
        return 0
    # one load serves both the report and the critical-path walk — a
    # merged multi-rank trace is easily hundreds of MB of JSON
    doc = load_trace(args.trace)
    print_report(args.trace, args.metrics, args.top, doc=doc)
    if args.plan:
        print_plan_profile(load_plan_profile(args.plan))
    if args.critical_path:
        cpt = _cp_tool()
        cp = cpt.critical_path(doc["traceEvents"], args.trace_id)
        if cp is None:
            print("\nno causally-traced request in this trace "
                  "(need CYLON_TPU_TRACE=1 plus an active request "
                  "context)", file=sys.stderr)
            return 2
        print()
        cpt.print_summary(cp)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
