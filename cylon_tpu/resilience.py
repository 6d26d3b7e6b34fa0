"""Resilience layer: error classification, bounded retry, and a
deterministic fault-injection harness for the out-of-core engine.

The reference survives scale by adding MPI ranks; the TPU analog streams
key-domain passes through one static XLA program (exec.py) — which makes
HBM pressure a *recoverable* condition: when a one-shot program exceeds
memory, decompose it into more, smaller passes and retry only the parts
that have not completed (the shape of "Memory-efficient array
redistribution through portable collective communication", PAPERS.md).
This module supplies the three primitives the engine and the table-level
one-shot ops share:

- **classification** — `Status.from_exception` (status.py) maps
  ``XlaRuntimeError``/PJRT failure text into the `Code` table
  (``RESOURCE_EXHAUSTED`` → `Code.OutOfMemory`, transient comm/deadline
  failures → `Code.ExecutionError`); `RETRYABLE_CODES` names which of
  those a plain retry may heal (OOM is NOT among them — it is healed by
  pass-splitting, not by doing the same allocation again);
- **RetryPolicy / retry_call** — bounded exponential backoff driven by
  ``CYLON_TPU_RETRY_MAX`` / ``CYLON_TPU_RETRY_BASE_S`` /
  ``CYLON_TPU_RETRY_MAX_S``;
- **fault injection** — named `fault_point(site)` probes (pass_dispatch,
  host_fetch, shuffle, shuffle_plan, oneshot_join, oneshot_groupby, ...)
  driven by a ``CYLON_TPU_FAULT_PLAN`` spec, so every recovery path is
  exercised deterministically on CPU in tier-1 tests — no real TPU OOM
  needed.  Injected faults carry the same message shapes PJRT emits, so
  they flow through the exact classification path real failures take.

Fault-plan spec grammar (';'- or ','-separated entries)::

    site            fire an OOM on the 1st hit of `site`
    site@N          fire an OOM on the Nth hit (1-based)
    site@N=kind     kind in {oom, timeout, comm, unknown}
    site@N+=kind    fire on EVERY hit >= N (persistent fault)

e.g. ``CYLON_TPU_FAULT_PLAN="pass_dispatch@2=oom;shuffle@1=timeout"``.
"""
from __future__ import annotations

import contextlib
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from . import config
from .obs import metrics as obs_metrics
from .obs import spans as obs_spans
from .status import Code, CylonError, Status

# Codes a plain bounded retry may heal.  OutOfMemory is deliberately
# absent: repeating an identical allocation cannot succeed — the engine
# heals OOM by splitting the remaining key-domain parts instead.
# Timeout (a pass-deadline overrun, durable.PassDeadline) retries like
# any transient: the hung collective/fetch may simply have been late.
RETRYABLE_CODES = frozenset({Code.ExecutionError, Code.Timeout})


def fault_delay_s() -> float:
    """Sleep injected by the ``delay`` fault kind
    (``CYLON_TPU_FAULT_DELAY_S``)."""
    return max(0.0, float(config.knob("CYLON_TPU_FAULT_DELAY_S")))


def max_oom_splits() -> int:
    """How many times the engine may double the pass count before a device
    OOM becomes fatal (``CYLON_TPU_MAX_OOM_SPLITS``, default 4 — a 16x
    refinement of the original plan)."""
    return max(0, int(config.knob("CYLON_TPU_MAX_OOM_SPLITS")))


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

_U64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    """splitmix64 finalizer on plain ints — the stateless hash behind
    seeded full-jitter (no RNG object, no hidden state: ``(seed, i)``
    always yields the same draw)."""
    x = (x + 0x9E3779B97F4A7C15) & _U64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _U64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _U64
    return x ^ (x >> 31)


def _jitter_u01(seed: int, i: int) -> float:
    """Deterministic uniform draw in [0, 1) for the ``i``-th retry under
    ``seed``."""
    return _splitmix64((seed & _U64) ^ _splitmix64(i)) / float(1 << 64)


@dataclass
class RetryPolicy:
    """Bounded exponential backoff for transient (`Code.ExecutionError`)
    failures.  ``max_retries`` is the number of RE-tries: an operation is
    attempted at most ``max_retries + 1`` times.

    ``jitter="full"`` draws each delay uniformly from ``[0, exp_delay]``
    (AWS full-jitter): when MANY clients back off from the same event —
    every survivor of a coordinator restart reconnecting at once — pure
    exponential backoff keeps them in lockstep and the whole herd
    thunders into the one-shot TCP accept loop on the same tick.  The
    draw is seeded-deterministic per (seed, retry_index): give each
    client a distinct ``jitter_seed`` (its rank) and the herd spreads,
    while tests replay the exact same schedule."""

    max_retries: int = 2
    base_s: float = 0.05
    max_s: float = 2.0
    multiplier: float = 2.0
    jitter: str = "none"            # "none" | "full"
    jitter_seed: int = 0
    sleep: Callable[[float], None] = field(default=time.sleep, repr=False)

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        return cls(
            max_retries=max(0, int(config.knob("CYLON_TPU_RETRY_MAX"))),
            base_s=max(0.0, float(config.knob("CYLON_TPU_RETRY_BASE_S"))),
            max_s=max(0.0, float(config.knob("CYLON_TPU_RETRY_MAX_S"))))

    def delay(self, retry_index: int) -> float:
        """Backoff before the ``retry_index``-th retry (0-based).  Safe
        for unbounded indices (long reconnect loops): the exponential
        saturates at ``max_s`` instead of overflowing, while the jitter
        draw keeps advancing with the index — a capped draw would freeze
        every late retry at one fixed per-seed delay."""
        if retry_index >= 64:
            d = self.max_s  # multiplier**i would overflow; it's capped
        else:
            d = min(self.base_s * (self.multiplier ** retry_index),
                    self.max_s)
        if self.jitter == "full":
            return d * _jitter_u01(self.jitter_seed, retry_index)
        return d

    def delays(self):
        for i in range(self.max_retries):
            yield self.delay(i)


def retry_call(fn, *, policy: Optional[RetryPolicy] = None, site: str = "op",
               retryable: frozenset = RETRYABLE_CODES,
               on_retry: Optional[Callable] = None) -> Tuple[object, int]:
    """Run ``fn()`` under ``policy``'s bounded backoff.

    Returns ``(result, attempts)``.  Exceptions whose classified code is
    not in ``retryable`` propagate unchanged (a TypeError must stay a
    TypeError); exhausting the retries raises `CylonError` with the
    classified code and the last failure's message.
    """
    policy = policy or RetryPolicy.from_env()
    attempts = 0
    while True:
        attempts += 1
        try:
            return fn(), attempts
        except Exception as e:
            st = Status.from_exception(e)
            if st.code not in retryable:
                raise
            retry_index = attempts - 1
            if retry_index >= policy.max_retries:
                raise CylonError(
                    st.code,
                    f"{site}: retries exhausted after {attempts} attempts: "
                    f"{st.msg}") from e
            # a retry is an event the trace must show: which site, which
            # attempt, and how the failure classified
            obs_spans.instant("retry", site=site, attempt=attempts,
                              code=st.code.name)
            obs_metrics.counter_add("retry.attempts")
            if on_retry is not None:
                on_retry(attempts, st)
            d = policy.delay(retry_index)
            if d > 0:
                policy.sleep(d)


# ---------------------------------------------------------------------------
# deterministic fault injection
# ---------------------------------------------------------------------------

# Message shapes mirror real PJRT/collective failure text so injected
# faults exercise the SAME classification path genuine failures take.
_KIND_MESSAGES = {
    "oom": ("RESOURCE_EXHAUSTED: injected fault at {site} (hit {hit}): "
            "attempting to allocate past HBM capacity"),
    "timeout": ("DEADLINE_EXCEEDED: injected fault at {site} (hit {hit}): "
                "operation timed out"),
    "comm": ("UNAVAILABLE: injected fault at {site} (hit {hit}): "
             "connection reset by peer"),
    "unknown": "INTERNAL: injected fault at {site} (hit {hit})",
    # non-raising kinds (durable-execution tests): `killhard` os._exit()s
    # the process at the probe (a kill -9 cannot be raised past),
    # `journal_corrupt` truncates the last committed spill and continues,
    # `hang` sleeps the probe past the active pass deadline
    "killhard": "injected hard kill at {site} (hit {hit})",
    "journal_corrupt": "injected spill corruption at {site} (hit {hit})",
    "hang": "injected hang at {site} (hit {hit})",
    # elastic-membership kinds (PR 6): `rank_kill` is killhard under an
    # elastic name (os._exit(137) at a pass boundary — a preempted /
    # kill -9'd gang member); `heartbeat_loss` raises at the agent's
    # heartbeat probe, which CATCHES it and goes permanently silent (a
    # network partition: the process keeps computing, the coordinator
    # hears nothing); `coordinator_loss` raises at the coordinator's
    # detector probe, which catches it and drops the control socket
    # (the membership ground truth dies mid-run)
    "rank_kill": "injected rank kill at {site} (hit {hit})",
    "heartbeat_loss": ("UNAVAILABLE: injected heartbeat loss at {site} "
                       "(hit {hit}): network error"),
    "coordinator_loss": ("UNAVAILABLE: injected coordinator loss at {site} "
                         "(hit {hit}): connection closed"),
    # serving kinds (PR 7): `tenant_flood` raises at the admission probe
    # (serve.admit) — the service converts it into a classified shed, the
    # deterministic stand-in for an admission resource check tripping;
    # `shed` raises at the dispatch probe (serve.dispatch) so a QUEUED
    # request sheds instead of running; `cache_evict_race` deletes the
    # last-opened journal's spill files while KEEPING the manifest — the
    # GC-eviction-races-a-reader window the result cache must survive by
    # re-executing, never by serving a torn journal
    # fleet-observability kind (PR 8): `delay` sleeps the probe for
    # CYLON_TPU_FAULT_DELAY_S and continues — a seeded straggler that
    # keeps heartbeating and computing correctly but arrives late at
    # every collective, so skew attribution has a known culprit
    "delay": "injected delay at {site} (hit {hit})",
    "tenant_flood": ("RESOURCE_EXHAUSTED: injected tenant flood at {site} "
                     "(hit {hit}): admission budget exceeded"),
    "shed": ("UNAVAILABLE: injected shed at {site} (hit {hit}): "
             "request shed under load"),
    "cache_evict_race": "injected cache evict race at {site} (hit {hit})",
    # control-plane survivability kinds (PR 11): `coordinator_restart`
    # raises at the coordinator's detector probe, which catches it and
    # restarts IN PLACE from the durable coordinator log — incarnation
    # and epoch bump, same address (the crash + takeover the reconnect
    # window must ride through); `coord_partition` raises at the agent's
    # RPC probe, which converts it into a ConnectionError — control
    # messages dropped one-way (agent -> coordinator) while the process
    # keeps computing; `coord_slow` sleeps the coordinator's verb
    # handler for CYLON_TPU_FAULT_DELAY_S and continues — delayed
    # replies that stress RPC timeouts without any loss
    "coordinator_restart": ("UNAVAILABLE: injected coordinator restart at "
                            "{site} (hit {hit}): takeover in progress"),
    "coord_partition": ("UNAVAILABLE: injected control partition at {site} "
                        "(hit {hit}): packet dropped"),
    "coord_slow": "injected slow control verb at {site} (hit {hit})",
    # tail-tolerance kinds (PR 16): `disk_full` raises OSError(ENOSPC)
    # at the spill-write probe — the real errno a full shared
    # CYLON_TPU_DURABLE_DIR produces, so the degraded-mode path is
    # exercised end to end; `replica_sick` sleeps the probe for
    # CYLON_TPU_FAULT_DELAY_S and continues — one replica's dispatch
    # path turns sustainedly slow while staying alive and correct, the
    # exact straggler hedged requests and health breakers must absorb
    "disk_full": ("RESOURCE_EXHAUSTED: injected disk full at {site} "
                  "(hit {hit}): no space left on device"),
    "replica_sick": "injected sick replica at {site} (hit {hit})",
    # journal-integrity kinds (PR 20): `bitrot` XOR-flips one mid-file
    # byte of a committed spill in the most recently opened run and
    # continues — silent storage decay (vs `journal_corrupt`'s blunt
    # truncation), the corruption the scrubber must find and read-repair
    # must heal; `sync_partial` is killhard under a replication name
    # (os._exit(137) at the per-file sync probe `journal_sync_file`) —
    # a replica dying mid-pull, which the spills-first/manifest-LAST
    # copy order must make invisible
    "bitrot": "injected spill bitrot at {site} (hit {hit})",
    "sync_partial": "injected partial journal sync at {site} (hit {hit})",
}

FAULT_KINDS = tuple(_KIND_MESSAGES)


class InjectedFault(RuntimeError):
    """Synthetic failure raised at a named `fault_point`."""

    def __init__(self, site: str, kind: str, hit: int):
        self.site = site
        self.kind = kind
        self.hit = hit
        super().__init__(_KIND_MESSAGES[kind].format(site=site, hit=hit))


@dataclass
class _FaultRule:
    site: str
    nth: int          # 1-based hit index on which to fire
    kind: str
    persistent: bool  # fire on every hit >= nth


class FaultPlan:
    """Parsed ``CYLON_TPU_FAULT_PLAN``: per-site hit counters + rules.

    Deterministic by construction: a site's Nth hit either always fires
    or never does, independent of timing.  ``hits`` and ``fired`` are
    exposed so tests can assert a site was actually exercised.

    Grammar extensions for chaos schedules (`FaultSchedule`): a
    ``seed=<int>`` entry anywhere in the spec seeds the plan, and a hit
    index may carry ``~J`` (``site@N~J=kind``) — the rule fires on a hit
    drawn deterministically from ``[N, N+J]`` by the seed and the rule's
    position, so one seed replays one exact multi-event timeline while
    different seeds explore different interleavings."""

    def __init__(self, rules: List[_FaultRule], spec: str = "",
                 seed: int = 0):
        self.rules = rules
        self.spec = spec
        self.seed = seed
        self.hits: Dict[str, int] = {}
        self.fired: List[Tuple[str, str, int]] = []  # (site, kind, hit)

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        raw_rules: List[Tuple[str, int, int, str, bool, str]] = []
        seed = 0
        for raw in spec.replace(",", ";").split(";"):
            entry = raw.strip()
            if not entry:
                continue
            if entry.startswith("seed="):
                try:
                    seed = int(entry[len("seed="):])
                except ValueError:
                    raise CylonError(Code.Invalid,
                                     f"bad seed in CYLON_TPU_FAULT_PLAN "
                                     f"entry {raw!r}")
                continue
            persistent = False
            kind = "oom"
            if "=" in entry:
                entry, kind = entry.split("=", 1)
                kind = kind.strip().lower()
                if entry.endswith("+"):
                    persistent = True
                    entry = entry[:-1]
            if kind not in _KIND_MESSAGES:
                raise CylonError(Code.Invalid,
                                 f"bad fault kind {kind!r} in "
                                 f"CYLON_TPU_FAULT_PLAN entry {raw!r} "
                                 f"(expected one of {FAULT_KINDS})")
            nth, jit = 1, 0
            if "@" in entry:
                entry, n = entry.split("@", 1)
                if "~" in n:
                    n, j = n.split("~", 1)
                    try:
                        jit = int(j)
                    except ValueError:
                        raise CylonError(Code.Invalid,
                                         f"bad hit jitter {j!r} in "
                                         f"CYLON_TPU_FAULT_PLAN entry "
                                         f"{raw!r}")
                    if jit < 0:
                        raise CylonError(Code.Invalid,
                                         f"hit jitter must be >= 0 in "
                                         f"{raw!r}")
                try:
                    nth = int(n)
                except ValueError:
                    raise CylonError(Code.Invalid,
                                     f"bad hit index {n!r} in "
                                     f"CYLON_TPU_FAULT_PLAN entry {raw!r}")
                if nth < 1:
                    raise CylonError(Code.Invalid,
                                     f"hit index must be >= 1 in {raw!r}")
            site = entry.strip()
            if not site:
                raise CylonError(Code.Invalid,
                                 f"empty site in CYLON_TPU_FAULT_PLAN "
                                 f"entry {raw!r}")
            raw_rules.append((site, nth, jit, kind, persistent, raw))
        rules: List[_FaultRule] = []
        for idx, (site, nth, jit, kind, persistent, _raw) in \
                enumerate(raw_rules):
            if jit:
                # the seed + rule position pick the exact hit: one spec
                # string is one timeline, replayable byte-for-byte
                nth += _splitmix64((seed & _U64) ^ _splitmix64(idx + 1)) \
                    % (jit + 1)
            rules.append(_FaultRule(site, nth, kind, persistent))
        return cls(rules, spec, seed=seed)

    def check(self, site: str) -> Optional[str]:
        """Record one hit of ``site``; return the fault kind to raise, or
        None."""
        hit = self.hits.get(site, 0) + 1
        self.hits[site] = hit
        for r in self.rules:
            if r.site != site:
                continue
            if hit == r.nth or (r.persistent and hit >= r.nth):
                self.fired.append((site, r.kind, hit))
                return r.kind
        return None


# Override plan (tests, via the fault_plan() context manager) wins over the
# env-driven plan; the env plan object persists while the spec string is
# unchanged so its hit counters accumulate across sites in one process.
_OVERRIDE_PLAN: Optional[FaultPlan] = None
_ENV_PLAN: Optional[FaultPlan] = None


def active_plan() -> Optional[FaultPlan]:
    global _ENV_PLAN
    if _OVERRIDE_PLAN is not None:
        return _OVERRIDE_PLAN
    spec = config.knob_raw("CYLON_TPU_FAULT_PLAN") or ""
    if not spec:
        _ENV_PLAN = None
        return None
    if _ENV_PLAN is None or _ENV_PLAN.spec != spec:
        _ENV_PLAN = FaultPlan.parse(spec)
    return _ENV_PLAN


def fault_point(site: str) -> None:
    """Injection probe: no-op unless an active fault plan names ``site``
    and its hit counter matches.  Costs one dict lookup when no plan is
    active — safe on hot paths."""
    plan = _OVERRIDE_PLAN
    if plan is None:
        if not config.knob_raw("CYLON_TPU_FAULT_PLAN"):
            return
        plan = active_plan()
        if plan is None:
            return
    kind = plan.check(site)
    if kind is not None:
        obs_spans.instant("fault.injected", site=site, kind=kind,
                          hit=plan.hits[site])
        obs_metrics.counter_add("fault.injected")
        if kind in ("killhard", "rank_kill", "sync_partial"):
            # simulate kill -9 / preemption: no cleanup, no atexit, no
            # flushed buffers — exactly what the journal must survive
            # (rank_kill is the elastic-membership spelling: survivors
            # must detect the silence, shrink, and resume; sync_partial
            # is the same death at the replication copy probe — the
            # manifest-LAST pull order must leave no visible run)
            os._exit(137)
        if kind == "journal_corrupt":
            from . import durable

            durable._corrupt_last_spill()
            return
        if kind == "bitrot":
            from . import durable

            durable._bitrot_last_run(plan.hits[site])
            return
        if kind == "cache_evict_race":
            from . import durable

            durable._evict_last_run_spills()
            return
        if kind == "hang":
            from . import durable

            time.sleep(max(1.5 * durable.deadline_s(), 0.05))
            return
        if kind in ("delay", "coord_slow", "replica_sick"):
            time.sleep(fault_delay_s())
            return
        if kind == "disk_full":
            # the genuine errno, so classification (and any errno-based
            # handling) is identical to a really-full disk
            import errno as _errno

            raise OSError(_errno.ENOSPC,
                          _KIND_MESSAGES[kind].format(site=site,
                                                      hit=plan.hits[site]))
        raise InjectedFault(site, kind, plan.hits[site])


@contextlib.contextmanager
def fault_plan(spec: str):
    """Install a fresh fault plan for the duration of the block (tests).
    Yields the `FaultPlan` so callers can assert on ``hits``/``fired``."""
    global _OVERRIDE_PLAN
    prev = _OVERRIDE_PLAN
    plan = FaultPlan.parse(spec)
    _OVERRIDE_PLAN = plan
    try:
        yield plan
    finally:
        _OVERRIDE_PLAN = prev


class FaultSchedule:
    """Composable, seeded multi-event chaos timeline.

    A builder over the `FaultPlan` grammar: chain :meth:`at` calls to
    compose any of the registered fault kinds — the elastic membership
    kinds, the durable-execution kinds, and the control-plane kinds
    ``coordinator_restart`` / ``coord_partition`` / ``coord_slow`` —
    into one spec string that ``CYLON_TPU_FAULT_PLAN`` (a worker's
    environment) or :meth:`install` (an in-process test) drives.  The
    schedule's ``seed`` resolves every jittered hit index at parse
    time, so a timeline is a pure function of (spec, seed): re-running
    it replays the exact same event order, and sweeping seeds explores
    different interleavings deterministically.

        sched = (FaultSchedule(seed=11)
                 .at("elastic.coordinator", "coordinator_restart", nth=2)
                 .at("elastic.rpc.r1", "coord_partition", nth=3, jitter=4)
                 .at("elastic.pass.r2", "delay", nth=1, persistent=True))
        env["CYLON_TPU_FAULT_PLAN"] = sched.spec()
    """

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._events: List[Tuple[str, str, int, int, bool]] = []

    def at(self, site: str, kind: str, nth: int = 1, jitter: int = 0,
           persistent: bool = False) -> "FaultSchedule":
        """Add one event: fire ``kind`` on a hit of ``site`` drawn from
        ``[nth, nth+jitter]`` by the schedule's seed.  Returns self for
        chaining; validation happens through `FaultPlan.parse`."""
        if kind not in _KIND_MESSAGES:
            raise CylonError(Code.Invalid,
                             f"bad fault kind {kind!r} in FaultSchedule "
                             f"(expected one of {FAULT_KINDS})")
        self._events.append((site, kind, int(nth), int(jitter),
                             bool(persistent)))
        return self

    def spec(self) -> str:
        """The composed ``CYLON_TPU_FAULT_PLAN`` spec string."""
        parts = [f"seed={self.seed}"] if self.seed else []
        for site, kind, nth, jitter, persistent in self._events:
            at = f"@{nth}" + (f"~{jitter}" if jitter else "")
            parts.append(f"{site}{at}{'+' if persistent else ''}={kind}")
        return ";".join(parts)

    def plan(self) -> FaultPlan:
        """The parsed (jitter-resolved) plan this schedule compiles to."""
        return FaultPlan.parse(self.spec())

    def install(self):
        """Context manager installing the schedule as the active fault
        plan (tests); yields the `FaultPlan` for hit/fired asserts."""
        return fault_plan(self.spec())


def classify(exc: BaseException) -> Code:
    """Shorthand: the classified `Code` of an exception."""
    return Status.from_exception(exc).code
