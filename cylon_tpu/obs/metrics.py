"""Process-local metrics: counters, gauges and histograms.

The accounting half of the observability subsystem: where ``obs.spans``
answers "where did the wall-clock go", this module answers "how much
work actually ran" — collective launches and bytes moved per shuffle
exchange (``shuffle.collective_launches`` / ``shuffle.bytes_sent``),
out-of-core refinements (``oom.refinements``), transient retries
(``retry.attempts``), jit-plan cache traffic (``plan_cache.hit`` /
``plan_cache.miss``) and the HBM watermarks (``hbm.live_bytes`` via
``jax.live_arrays``; ``hbm.bytes_in_use`` / ``hbm.peak_bytes`` from
``memory_stats()`` of the fullest local device, where reported).

Everything is plain dict arithmetic on the host — no jax dependency, no
locks on the hot counters (CPython's GIL makes the single add/assign
effectively atomic, the same contract the PR-0 timing registry relied
on).  ``snapshot()`` is deterministic: keys come out sorted, so two runs
recording the same work in any order serialize identically.
"""
from __future__ import annotations

import bisect
import sys
from typing import Dict, Optional

_counters: Dict[str, float] = {}
_gauges: Dict[str, float] = {}
_hists: Dict[str, "_Hist"] = {}


#: fixed cumulative-bucket boundaries (OpenMetrics ``le`` semantics): a
#: 1-2.5-5 ladder through 1e6, decades beyond (the >1e6 range is byte
#: counts where decade resolution suffices) — wide enough to cover both
#: millisecond latencies and byte counts with ONE boundary set, and
#: FIXED so histograms recorded by different ranks (or different runs)
#: merge by plain per-key addition (``fleet.merge_hist``) and render as
#: Prometheus cumulative buckets without rebinning.  Changing this set
#: breaks merges against already-persisted snapshots (flight dumps,
#: heartbeat ledgers) — extend only with a version bump.
LE_BUCKETS: tuple = (1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 2500,
                     5000, 10000, 25000, 50000, 100000, 250000, 500000,
                     1000000, 10000000, 100000000, 1000000000)


class _Hist:
    """Fixed-shape histogram: count/sum/min/max plus power-of-two bucket
    counts (bucket i holds values in [2**i, 2**(i+1)); negatives and
    zeros land in bucket 0).  ``as_dict`` additionally emits the fixed
    CUMULATIVE ``le`` buckets (``LE_BUCKETS`` + "+Inf") the OpenMetrics
    exposition needs — per-boundary counts are kept non-cumulative
    internally (one increment per observe) and accumulated at snapshot
    time."""

    __slots__ = ("count", "sum", "min", "max", "buckets", "le_counts")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.buckets: Dict[int, int] = {}
        # one slot per LE_BUCKETS boundary + the +Inf overflow slot
        self.le_counts = [0] * (len(LE_BUCKETS) + 1)

    def observe(self, value: float) -> None:
        v = float(value)
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        b = max(0, int(v).bit_length() - 1) if v >= 1 else 0
        self.buckets[b] = self.buckets.get(b, 0) + 1
        i = bisect.bisect_left(LE_BUCKETS, v)
        self.le_counts[i] += 1

    def le_dict(self) -> Dict[str, int]:
        """Cumulative {boundary: count of observations <= boundary},
        keys are decimal strings plus "+Inf" (== count)."""
        out: Dict[str, int] = {}
        acc = 0
        for bound, n in zip(LE_BUCKETS, self.le_counts):
            acc += n
            out[str(bound)] = acc
        out["+Inf"] = acc + self.le_counts[-1]
        return out

    def as_dict(self) -> Dict[str, object]:
        return {"count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max,
                "buckets": {str(k): self.buckets[k]
                            for k in sorted(self.buckets)},
                "le": self.le_dict()}


def counter_add(name: str, value: float = 1) -> None:
    _counters[name] = _counters.get(name, 0) + value


def counter_value(name: str) -> float:
    return _counters.get(name, 0)


def gauge_set(name: str, value: float) -> None:
    _gauges[name] = float(value)


def gauge_max(name: str, value: float) -> None:
    """Watermark gauge: keeps the maximum ever set."""
    v = float(value)
    cur = _gauges.get(name)
    if cur is None or v > cur:
        _gauges[name] = v


def hist_observe(name: str, value: float) -> None:
    h = _hists.get(name)
    if h is None:
        h = _hists[name] = _Hist()
    h.observe(value)


def record_hbm_watermark() -> int:
    """Sample device memory; returns the live device-array bytes
    (``jax.live_arrays``), which is also the ``hbm.live_bytes`` watermark
    gauge.  Where the backend reports ``memory_stats()``, the fullest
    local device also sets ``hbm.bytes_in_use`` and the
    ``hbm.peak_bytes`` watermark.  Host-side and jax-optional: 0 when jax
    was never imported."""
    jax = sys.modules.get("jax")
    if jax is None or not hasattr(jax, "live_arrays"):
        return 0
    stats = [s for s in (d.memory_stats() for d in jax.local_devices()) if s]
    if stats:
        gauge_set("hbm.bytes_in_use",
                  max(s.get("bytes_in_use", 0) for s in stats))
        gauge_max("hbm.peak_bytes",
                  max(s.get("peak_bytes_in_use", 0) for s in stats))
    total = 0
    for a in jax.live_arrays():
        total += getattr(a, "nbytes", 0) or 0
    gauge_max("hbm.live_bytes", total)
    return total


def snapshot() -> Dict[str, object]:
    """Deterministic flat snapshot: {"counters": {...}, "gauges": {...},
    "histograms": {...}} with every key level sorted."""
    return {
        "counters": {k: _counters[k] for k in sorted(_counters)},
        "gauges": {k: _gauges[k] for k in sorted(_gauges)},
        "histograms": {k: _hists[k].as_dict() for k in sorted(_hists)},
    }


def reset() -> None:
    _counters.clear()
    _gauges.clear()
    _hists.clear()
