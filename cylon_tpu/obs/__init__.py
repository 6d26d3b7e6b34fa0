"""cylon_tpu.obs — structured tracing, metrics, and Perfetto export.

The fourth leg after robustness (PR 1), perf (PR 2) and static analysis
(PR 3): PR 3's budget gates prove what a plan WOULD launch; this
subsystem records what actually ran — nested wall-clock spans over every
hot path (``obs.spans``), counters/gauges/histograms for collective
launches, bytes moved, retries, OOM refinements and plan-cache traffic
(``obs.metrics``), and Chrome-trace/Perfetto + flat-JSON artifacts with
per-rank naming (``obs.export``).  Zero hard dependencies (jax is
consulted through ``sys.modules`` only), host-side by construction.

Knobs (all runtime scope, registered in ``config.KNOBS``):
``CYLON_TPU_TRACE`` (auto: aggregate stopwatch only; 1: event buffer for
export; 0: alloc-free no-op), ``CYLON_TPU_TRACE_DIR``,
``CYLON_TPU_TRACE_BUFFER_CAP``.  In every mode but off a span is also a
``jax.profiler.TraceAnnotation``; ``stage`` names the kernel stages
(``STAGES``) inside the programs.
"""
from __future__ import annotations

from . import export  # noqa: F401
from . import fleet  # noqa: F401
from . import metrics  # noqa: F401
from . import spans  # noqa: F401
from . import tracectx  # noqa: F401
from .spans import STAGES, instant, span, stage  # noqa: F401
