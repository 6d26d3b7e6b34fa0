#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, how many events each
has, the names that take most time and the stats of one event.  For a
human who has to write or mend a reader against a trace.

    python3 bench/tools/inspect_trace.py <file.xplane.pb | trace dir>
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace_reduce  # noqa: E402


def main(path: str) -> int:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            totals = {}
            for ev in events:
                totals[ev.name] = totals.get(ev.name, 0) + ev.duration_ns
            for name, ns in sorted(totals.items(), key=lambda kv: -kv[1])[:12]:
                print(f"    {ns * 1e-6:12.3f} ms  {name[:100]}")
            if events:
                ev = max(events, key=lambda e: e.duration_ns)
                print("    stats of the longest:",
                      {k: str(v)[:80] for k, v in ev.stats})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
