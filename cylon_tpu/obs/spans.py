"""Structured tracing spans: the event substrate behind Perfetto export.

Replaces the PR-0 stopwatch (``utils/timing.py``, now a thin shim over
this module) with a process-local EVENT BUFFER — every span records
(monotonic ns start, duration, thread id, nesting depth, attributes) so
``obs.export`` can emit a Chrome-trace/Perfetto JSON showing exactly
where wall-clock went, not just per-name totals.

Three operating modes, selected by the ``CYLON_TPU_TRACE`` registry knob
(read live on every ``span()`` call, so ``config.knob_env`` works):

- ``auto`` (default) — the always-on aggregate stopwatch only: each span
  costs two ``perf_counter_ns`` reads and two dict updates (the PR-0
  ``utils.timing`` behavior; benchmarks read phase breakdowns via
  ``aggregate_report()``).  No event is buffered.
- ``1`` / ``on`` — aggregates PLUS the bounded event buffer
  (``CYLON_TPU_TRACE_BUFFER_CAP`` events; past it events are dropped and
  counted, never grown) for export.
- ``0`` / ``off`` — a true no-op: ``span()`` returns a process-wide
  singleton null context manager and touches nothing (the alloc-free
  fast path tests/test_obs.py pins).

Host-side only, by construction: a span measures host wall-clock between
``__enter__`` and ``__exit__`` and never reads a device value, so spans
are legal inside jit/shard_map bodies (they then measure TRACE time and
appear as children of the enclosing plan-build span — cylint CY101 stays
green because no tracer is read).  Device execution is asynchronous, so
device time lands in whichever span performed the blocking fetch.

The profiler's clock: in every mode but off a span is also a
``jax.profiler.TraceAnnotation`` of the same name (attributes as its
keyword arguments), so inside a ``jax.profiler`` session the program's
spans lie beside the device's operations, and ``stage(name)`` is the
``jax.named_scope`` that puts one of ``STAGES`` into the metadata of the
operations traced under it (``tools/trace_report.py --device`` reads
both).  With no session open an annotation costs about a microsecond.
"""
from __future__ import annotations

import logging
import sys
import threading
import time
from collections import deque
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

from .. import config
from . import tracectx

log = logging.getLogger("cylon_tpu")

OFF = "off"
AGGREGATE = "aggregate"
EVENTS = "events"

_MODE_OF = {"0": OFF, "off": OFF, "auto": AGGREGATE,
            "1": EVENTS, "on": EVENTS}

#: aggregate of every span closed at depth 0: host time inside the program
ROOT = "obs.root"

#: the kernel stages a device operation can belong to — the one list of
#: their names (``stage`` refuses any other); at most 16
STAGES = ("join.ranges", "join.emit", "join.expand", "join.gather_left",
          "join.gather_right", "groupby.sort", "groupby.boundaries",
          "groupby.keys", "groupby.gather", "groupby.reduce", "sort.keys",
          "sort.permute", "compact.partition", "compact.permute",
          "plane.pack", "plane.unpack")


class Event(NamedTuple):
    """One buffered trace event.  ``ts``/``dur`` are monotonic
    nanoseconds (``time.perf_counter_ns``); ``ph`` is the Chrome-trace
    phase — "X" complete span, "i" instant.  ``trace`` is the causal
    identity triple ``(trace_id, span_id, parent_span_id)`` when a
    request context (obs.tracectx) was active, else None."""

    name: str
    ts: int
    dur: int
    tid: int
    depth: int
    ph: str
    attrs: Optional[Dict[str, object]]
    trace: Optional[Tuple[str, str, Optional[str]]] = None


_events: List[Event] = []
_dropped = 0
# guards buffer membership (record vs retention discard): only taken
# when event buffering is ON — the aggregate-only default never touches
# it.  Readers (events(), exports) stay lock-free: tuple(_events) is one
# GIL-atomic C call and the list is only ever appended or rebuilt whole.
_buf_lock = threading.Lock()
_totals: Dict[str, float] = {}
_counts: Dict[str, int] = {}
_tls = threading.local()

# flight-recorder ring: the most recent events, kept in EVERY enabled
# mode (aggregate included) so a terminal-event dump (obs.fleet) has
# context even when the user never armed CYLON_TPU_TRACE=1.  Unlike the
# export buffer it overwrites oldest-first — a post-mortem wants the
# events LEADING UP to the failure, not the run's first N.
_ring: "deque[Event]" = deque(maxlen=512)

# CYLON_TPU_DEBUG log-on-exit (the PR-0 utils.timing behavior, preserved
# through the shim): initialized from the knob, flipped by enable_log()
_log_enabled = bool(config.knob("CYLON_TPU_DEBUG"))


def mode() -> str:
    """The live tracing mode: "off" | "aggregate" | "events"
    (``CYLON_TPU_TRACE``, read per call so knob_env overrides apply)."""
    return _MODE_OF.get(str(config.knob("CYLON_TPU_TRACE")), AGGREGATE)


def enabled() -> bool:
    return mode() != OFF


def events_enabled() -> bool:
    return mode() == EVENTS


def buffer_cap() -> int:
    return max(1, int(config.knob("CYLON_TPU_TRACE_BUFFER_CAP")))


def ring_cap() -> int:
    """``CYLON_TPU_FLIGHT_RING_CAP``: flight-recorder ring size (0 off)."""
    return max(0, int(config.knob("CYLON_TPU_FLIGHT_RING_CAP")))


def _ring_record(ev: Event) -> None:
    global _ring
    cap = ring_cap()
    if cap <= 0:
        return
    if _ring.maxlen != cap:
        _ring = deque(_ring, maxlen=cap)
    _ring.append(ev)


def ring_events() -> Tuple[Event, ...]:
    """Snapshot of the flight-recorder ring, oldest first."""
    return tuple(_ring)


def enable_log(on: bool = True) -> None:
    """Flip the per-span INFO log (the old ``utils.timing.enable``)."""
    global _log_enabled
    _log_enabled = on


def log_enabled() -> bool:
    return _log_enabled


def _depth() -> int:
    return getattr(_tls, "depth", 0)


def _record(ev: Event) -> None:
    global _dropped
    with _buf_lock:
        if len(_events) >= buffer_cap():
            _dropped += 1
            return
        _events.append(ev)


class _NullSpan:
    """The disabled-mode singleton: every method is a no-op and ``span()``
    hands out the same instance, so fully-disabled tracing allocates
    nothing per call site."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "attrs", "_t0", "_d", "_buffer", "_ring", "_trace",
                 "_ann")

    def __init__(self, name: str, attrs: Optional[Dict[str, object]],
                 buffer: bool, ring: bool):
        self.name = name
        self.attrs = attrs
        self._buffer = buffer
        self._ring = ring
        self._trace = None
        # the same span on the profiler's clock; jax through sys.modules,
        # so obs stays importable without it
        jax = sys.modules.get("jax")
        self._ann = (None if jax is None else
                     jax.profiler.TraceAnnotation(name, **(attrs or {})))

    def set(self, **attrs) -> "_Span":
        """Attach/refresh attributes after entry (e.g. a row count known
        only once the pass fetched)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    def __enter__(self) -> "_Span":
        if self._buffer or self._ring:
            # causal identity: become a child span of the active request
            # context (None — the common case — costs one contextvar read)
            self._trace = tracectx.push_span()
        self._d = _depth()
        _tls.depth = self._d + 1
        self._t0 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        t1 = time.perf_counter_ns()
        _tls.depth = self._d
        dur = t1 - self._t0
        for name in (self.name, ROOT) if self._d == 0 else (self.name,):
            _totals[name] = _totals.get(name, 0.0) + dur * 1e-9
            _counts[name] = _counts.get(name, 0) + 1
        if self._buffer or self._ring:
            tr = None
            if self._trace is not None:
                ctx, tok = self._trace
                tracectx.pop_span(tok)
                tr = ctx.triple()
            ev = Event(self.name, self._t0, dur,
                       threading.get_ident(), self._d, "X", self.attrs, tr)
            if self._buffer:
                _record(ev)
            if self._ring:
                _ring_record(ev)
        if _log_enabled:
            log.info("%s took %.3f ms", self.name, dur * 1e-6)
        return False


def span(name: str, **attrs):
    """Context manager timing one named phase.

    Aggregate totals always accumulate (unless tracing is fully off);
    under ``CYLON_TPU_TRACE=1`` the span also lands in the event buffer
    with its attributes.  Use ``as s`` + ``s.set(...)`` for attributes
    known only at exit."""
    m = mode()
    if m == OFF:
        return _NULL
    # the ring knob resolves ONCE per span, not per boundary, so
    # enter/exit stay at two perf_counter reads and two dict updates
    return _Span(name, attrs or None, m == EVENTS, ring_cap() > 0)


def stage(name: str):
    """``jax.named_scope(name)`` for one of ``STAGES``: the operations
    traced under it carry the stage in their metadata (``op_name``), and
    with it into a device trace.  Metadata only — the program, its cache
    key and its jaxpr are what they were.  In every tracing mode, off
    included: the name is part of the compiled program, not of a run."""
    if name not in STAGES:
        raise ValueError(f"{name!r} is not one of obs.STAGES")
    jax = sys.modules.get("jax")
    return _NULL if jax is None else jax.named_scope(name)


def instant(name: str, **attrs) -> None:
    """Record a zero-duration instant event (retry, injected fault, OOM
    refinement).  Counted in the aggregates; buffered only under
    ``CYLON_TPU_TRACE=1``."""
    m = mode()
    if m == OFF:
        return
    _counts[name] = _counts.get(name, 0) + 1
    _totals.setdefault(name, 0.0)
    if m == EVENTS or ring_cap() > 0:
        c = tracectx.current()
        ev = Event(name, time.perf_counter_ns(), 0,
                   threading.get_ident(), _depth(), "i", attrs or None,
                   None if c is None else c.triple())
        if m == EVENTS:
            _record(ev)
        _ring_record(ev)


def events() -> Tuple[Event, ...]:
    """Snapshot of the buffered events, in record order."""
    return tuple(_events)


def discard_trace(trace_id: str) -> int:
    """Tail-based retention's discard half: remove buffered events
    stamped with ``trace_id`` (a fast-and-healthy request closing), and
    return how many were removed.  The flight ring is deliberately
    untouched (a post-mortem wants the most recent events whoever owned
    them) and the drop counter is MONOTONE — retention discards are
    accounted separately (``trace.tail_dropped``), never by un-counting
    overflow drops.  One O(buffer) rebuild under the record lock, so a
    concurrent request's append can never be lost mid-rebuild; the cost
    is bounded by the buffer cap and paid only on a losing close."""
    with _buf_lock:
        before = len(_events)
        _events[:] = [e for e in _events
                      if e.trace is None or e.trace[0] != trace_id]
        return before - len(_events)


def dropped() -> int:
    """Events discarded because the buffer was at capacity."""
    return _dropped


def aggregate_report() -> Dict[str, Tuple[float, int]]:
    """{span name: (total seconds, call count)} — the PR-0
    ``utils.timing.report`` surface."""
    return {k: (_totals[k], _counts.get(k, 0)) for k in _totals}


def reset_aggregates() -> None:
    """Clear the aggregate stopwatch totals ONLY — buffered events and
    the drop counter survive, so a benchmark clearing phase totals
    between phases (the historical ``utils.timing.reset``) cannot
    truncate a pending Perfetto export."""
    _totals.clear()
    _counts.clear()


def reset() -> None:
    """Clear the event buffer, the flight ring, the drop counter and the
    aggregates."""
    global _dropped
    _events.clear()
    _ring.clear()
    _dropped = 0
    reset_aggregates()
