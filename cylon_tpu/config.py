"""Builder-style option objects and the environment-knob registry.

The reference has no global flag system; options travel as small builder
objects (SURVEY §5): ``JoinConfig`` (cpp/src/cylon/join/join_config.hpp:22-89),
``SortOptions`` (table.hpp:365-373), CSV/Parquet options (under io/).  Same
here; the IO options live in cylon_tpu.io.

This module is also the ONE place the package reads ``CYLON_TPU_*``
environment knobs (the other sanctioned reader is
``utils/compile_cache.py``, which must work before the package imports).
``KNOBS`` is the authoritative declarative table — name, type, default,
scope (trace-time vs runtime), jit-plan cache-key participation — and
``knob()`` / ``knob_raw()`` are the only accessors call sites may use.
``cylint`` (``python -m cylon_tpu.analysis``) bans stray ``os.environ``
reads elsewhere in the package (rule CY102) and checks that every
trace-scope knob reachable from a jit-plan body participates in that
plan's cache key (rule CY103) — the exact bug class
``CYLON_TPU_SHUFFLE_PACK`` had to be hand-keyed against in PR 2.
"""
from __future__ import annotations

import contextlib
import enum
import os
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union


class JoinType(enum.IntEnum):
    """reference: join/join_config.hpp JoinType."""

    INNER = 0
    LEFT = 1
    RIGHT = 2
    FULL_OUTER = 3


class JoinAlgorithm(enum.IntEnum):
    """reference: join/join_config.hpp JoinAlgorithm {SORT, HASH}.

    Two genuinely distinct kernel families, like the reference's
    do_(inplace_)sorted_join vs do_hash_join (join.cpp:515-543): SORT is
    the fused combined-lexsort merge (ops/join.py), HASH the
    open-addressing build/probe over a device hash table
    (ops/hash_join.py) that never sorts the probe side.
    """

    SORT = 0
    HASH = 1


_JOIN_TYPE_OF = {
    "inner": JoinType.INNER, "left": JoinType.LEFT, "right": JoinType.RIGHT,
    "fullouter": JoinType.FULL_OUTER, "full_outer": JoinType.FULL_OUTER,
    "outer": JoinType.FULL_OUTER,
}
_ALGO_OF = {"sort": JoinAlgorithm.SORT, "hash": JoinAlgorithm.HASH}


def _as_tuple(v) -> Tuple[int, ...]:
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,)


@dataclass(frozen=True)
class JoinConfig:
    """reference: join/join_config.hpp:29-89 (type × algorithm × key columns
    × output-name prefixes)."""

    join_type: JoinType = JoinType.INNER
    algorithm: JoinAlgorithm = JoinAlgorithm.SORT
    left_on: Tuple = ()
    right_on: Tuple = ()
    left_prefix: str = "l_"
    right_prefix: str = "r_"

    @staticmethod
    def of(join_type: Union[str, JoinType], algorithm: Union[str, JoinAlgorithm] = "sort",
           left_on=(), right_on=(), left_prefix: str = "l_", right_prefix: str = "r_") -> "JoinConfig":
        if isinstance(join_type, str):
            join_type = _JOIN_TYPE_OF[join_type.lower().replace("-", "_")]
        if isinstance(algorithm, str):
            algorithm = _ALGO_OF[algorithm.lower()]
        return JoinConfig(join_type, algorithm, _as_tuple(left_on), _as_tuple(right_on),
                          left_prefix, right_prefix)

    # reference-parity factories (join_config.hpp InnerJoin/LeftJoin/...)
    @staticmethod
    def InnerJoin(left_on, right_on, algorithm="sort") -> "JoinConfig":
        return JoinConfig.of("inner", algorithm, left_on, right_on)

    @staticmethod
    def LeftJoin(left_on, right_on, algorithm="sort") -> "JoinConfig":
        return JoinConfig.of("left", algorithm, left_on, right_on)

    @staticmethod
    def RightJoin(left_on, right_on, algorithm="sort") -> "JoinConfig":
        return JoinConfig.of("right", algorithm, left_on, right_on)

    @staticmethod
    def FullOuterJoin(left_on, right_on, algorithm="sort") -> "JoinConfig":
        return JoinConfig.of("full_outer", algorithm, left_on, right_on)


@dataclass(frozen=True)
class SortOptions:
    """reference: table.hpp:365-373 SortOptions{ascending, num_bins,
    num_samples} — bins/samples drive the sampled-histogram range
    partitioner of DistributedSort."""

    ascending: bool = True
    num_bins: int = 0        # 0 -> 16 * world_size (reference default)
    num_samples: int = 0     # 0 -> min(row_count, 4096) per shard
    nulls_first: bool = True


# ---------------------------------------------------------------------------
# environment-knob registry
# ---------------------------------------------------------------------------

#: scope values: "trace" — the value is read while tracing a jit program
#: (flipping it changes the traced computation, so it must participate in
#: every jit-plan cache key; ``trace_cache_token()`` carries them all);
#: "runtime" — read on the host outside any trace (retry budgets, IO
#: fallbacks, debug switches); flipping it never invalidates a compiled
#: program.
TRACE = "trace"
RUNTIME = "runtime"


@dataclass(frozen=True)
class Knob:
    """One row of the declarative environment-knob table.

    ``accessors`` names the package functions (dotted module-qualified)
    through which call sites consume the knob — cylint's cache-key rule
    (CY103) uses them to map knob *uses inside traced bodies* back to the
    registry row.
    """

    name: str
    kind: str                       # "str" | "int" | "float" | "bool" | "enum"
    default: object
    scope: str                      # TRACE | RUNTIME
    cache_key: bool = False         # must participate in jit-plan cache keys
    choices: Tuple[str, ...] = ()   # for kind == "enum"
    accessors: Tuple[str, ...] = ()
    help: str = ""


_K = Knob

KNOBS: Dict[str, Knob] = {k.name: k for k in [
    # -- trace-scope knobs: every one of these changes the traced program --
    _K("CYLON_TPU_SHUFFLE_PACK", "enum", "auto", TRACE, cache_key=True,
       choices=("1", "on", "packed", "0", "off", "perbuf", "auto"),
       accessors=("cylon_tpu.parallel.plane.pack_enabled",),
       help="Shuffle exchange realization: one bit-packed u32 plane per "
            "collective (packed) vs one collective per buffer per column "
            "(perbuf); auto packs on TPU-family backends."),
    _K("CYLON_TPU_SHUFFLE_COMPRESS", "enum", "auto", TRACE, cache_key=True,
       choices=("1", "on", "0", "off", "auto"),
       accessors=("cylon_tpu.parallel.plane.compress_enabled",),
       help="Compress the packed shuffle plane between pack and exchange: "
            "integer columns narrow to their observed range (offset + "
            "reduced bit width), low-cardinality string columns exchange "
            "dictionary codes plus one small all-gathered dictionary, "
            "string data/length fields truncate to the observed extent — "
            "bit-exact by construction.  Rides the packed plane "
            "(CYLON_TPU_SHUFFLE_PACK); auto enables on TPU-family "
            "backends.  The observed spec is static layout, so it also "
            "enters every exchange plan cache key (cylint CY109)."),
    _K("CYLON_TPU_ACCUM", "enum", "auto", TRACE, cache_key=True,
       choices=("wide", "narrow", "auto"),
       accessors=("cylon_tpu.precision.accumulation_mode",
                  "cylon_tpu.precision.narrow",
                  "cylon_tpu.precision.float_acc",
                  "cylon_tpu.precision.float_acc_for",
                  "cylon_tpu.precision.int_acc",
                  "cylon_tpu.precision.count_acc"),
       help="Accumulator widths for sums/stats: wide (f64/i64) vs narrow "
            "(f32/i32-native); auto narrows on TPU-family backends."),
    _K("CYLON_TPU_STREAM_BATCH_CAP", "int", 0, TRACE, cache_key=True,
       accessors=("cylon_tpu.stream.incremental.batch_cap",),
       help="Fixed device capacity per streaming micro-batch (rows); 0 "
            "(default) derives pow2ceil(batch rows) per batch.  Trace-"
            "scope cache key: padded batch shape is part of the stream "
            "kernel's traced program AND of the persisted-state "
            "namespace — flipping it must re-derive state from the "
            "batch log, never combine across capacity regimes."),
    _K("CYLON_TPU_STREAM_STATE_CAP", "int", 0, TRACE, cache_key=True,
       accessors=("cylon_tpu.stream.incremental.state_cap",),
       help="Floor for the incremental group-by's persisted-state group "
            "capacity (rows); 0 (default) derives from the first "
            "batch's group count.  State still regrows by the "
            "deterministic overflow-restart rule.  Trace-scope cache "
            "key for the same reason as CYLON_TPU_STREAM_BATCH_CAP."),
    # -- plan-scope / runtime knobs ----------------------------------------
    _K("CYLON_TPU_SHUFFLE", "enum", "auto", RUNTIME,
       choices=("ragged", "bucketed", "auto"),
       help="Exchange collective family: RaggedAllToAll vs fixed-bucket "
            "all_to_all; auto probes the backend.  Selected at plan-build "
            "time on the host (the two families build differently-keyed "
            "plans, so no cache-key participation is needed)."),
    _K("CYLON_TPU_PLAN", "enum", "auto", RUNTIME,
       choices=("1", "on", "0", "off", "auto"),
       accessors=("cylon_tpu.plan.executor.planner_enabled",),
       help="Logical-plan optimizer for Table.plan() pipelines: shuffle "
            "elision, column pruning, scan sharing and fused local "
            "kernels (auto/on, default) vs eager per-op lowering (off — "
            "the A/B baseline).  A host-side plan-build choice like "
            "CYLON_TPU_SHUFFLE: each mode builds differently-keyed stage "
            "programs, so no cache-key participation; results are "
            "bit-identical either way."),
    _K("CYLON_TPU_PLAN_ADAPTIVE", "enum", "auto", RUNTIME,
       choices=("1", "on", "0", "off", "auto"),
       accessors=("cylon_tpu.plan.optimizer.planner_adaptive",),
       help="Statistics-driven physical strategy selection on top of the "
            "CYLON_TPU_PLAN optimizer: broadcast-hash joins for "
            "dimension-sized sides and skew-salted NUNIQUE repartition, "
            "picked by the plan/cost.py model from the stats catalog (or "
            "conservative metadata bounds when no catalog exists).  "
            "auto (default) is OFF this release — opt in with 1/on until "
            "the TPU calibration round lands.  off is bit-identical to "
            "the PR-9 planner.  Chosen strategies are folded into the "
            "plan fingerprint and stage keys, so no cache-key "
            "participation is needed."),
    _K("CYLON_TPU_PLAN_BROADCAST_BYTES", "int", 1 << 20, RUNTIME,
       accessors=("cylon_tpu.plan.cost.broadcast_threshold_bytes",),
       help="Adaptive-planner broadcast-hash-join threshold: a join side "
            "whose estimated payload is at most this many bytes may be "
            "all_gather-replicated instead of hash-shuffled (cost model "
            "still has to agree).  Per-shard post-gather footprint is "
            "world x this bound."),
    _K("CYLON_TPU_PLAN_SKEW_SALT", "float", 4.0, RUNTIME,
       accessors=("cylon_tpu.plan.cost.skew_salt_factor",),
       help="Adaptive-planner skew threshold: salt a NUNIQUE repartition "
            "when the catalog-observed shard-placement skew "
            "(max/mean shard rows) of the aggregate's input meets this "
            "factor.  Salting is exact (value-hash bucketing + integer "
            "COUNTSUM combine) but costs one extra small exchange."),
    _K("CYLON_TPU_MAX_STRING_WIDTH", "int", 4096, RUNTIME,
       help="Widest byte matrix a string column may ingest without an "
            "explicit string_width= (HBM guard)."),
    _K("CYLON_TPU_ONESHOT_FALLBACK", "bool", True, RUNTIME,
       help="Allow a single-shard one-shot op that dies of device OOM to "
            "fall back to the chunked out-of-core engine."),
    _K("CYLON_TPU_FALLBACK_PASSES", "int", 4, RUNTIME,
       help="Initial pass count for the one-shot -> chunked OOM fallback."),
    _K("CYLON_TPU_CHUNK_PRESORT", "bool", True, RUNTIME,
       help="Pre-group host rows by pass id once (O(n)) instead of masking "
            "per pass (O(n x passes)) in the chunked engine."),
    _K("CYLON_TPU_PREFETCH", "bool", True, RUNTIME,
       help="Overlap host slicing of pass p+1 with device execution of "
            "pass p in the chunked engine."),
    _K("CYLON_TPU_NO_NATIVE_IO", "bool", False, RUNTIME,
       help="Disable the native (C++) CSV/Arrow fast paths; use pyarrow."),
    _K("CYLON_TPU_NO_NATIVE", "bool", False, RUNTIME,
       help="Disable loading the native kernel library entirely."),
    _K("CYLON_TPU_MAX_OOM_SPLITS", "int", 4, RUNTIME,
       help="How many times the out-of-core engine may double the pass "
            "count before a device OOM becomes fatal."),
    _K("CYLON_TPU_RETRY_MAX", "int", 2, RUNTIME,
       help="Transient-failure retry budget (RetryPolicy.from_env)."),
    _K("CYLON_TPU_RETRY_BASE_S", "float", 0.05, RUNTIME,
       help="Base backoff seconds for transient retries."),
    _K("CYLON_TPU_RETRY_MAX_S", "float", 2.0, RUNTIME,
       help="Backoff ceiling seconds for transient retries."),
    _K("CYLON_TPU_FAULT_PLAN", "str", "", RUNTIME,
       help="Deterministic fault-injection plan: `site[@N][+][=kind]` "
            "entries joined by `;` (resilience.FaultPlan.parse), e.g. "
            "`pass_dispatch@2=oom;shuffle@1=timeout`; empty disables."),
    _K("CYLON_TPU_FP_SALT", "str", "", RUNTIME,
       help="Opaque salt mixed into every durable run/plan fingerprint: "
            "a measurement sets a per-run value so it can never be served "
            "from the journal result cache; empty (default) keeps "
            "fingerprints stable across runs."),
    _K("CYLON_TPU_DURABLE_DIR", "str", "", RUNTIME,
       accessors=("cylon_tpu.durable.durable_dir",
                  "cylon_tpu.durable.enabled"),
       help="Root directory for the durable-execution run journal: each "
            "fingerprinted chunked run spills completed passes as "
            "checksummed Arrow IPC files + an append-only manifest, so a "
            "fresh process re-invoking the same run resumes mid-plan "
            "(kill -9 safe).  Empty (default) disables journaling."),
    _K("CYLON_TPU_PASS_DEADLINE_S", "float", 0.0, RUNTIME,
       accessors=("cylon_tpu.durable.deadline_s",
                  "cylon_tpu.durable.pass_deadline"),
       help="Per-pass wall-clock budget: a watchdog thread fires "
            "deadline.fired when a pass runs past it and the pass is "
            "classified Code.Timeout (retried like a transient).  "
            "0 (default) disables."),
    _K("CYLON_TPU_DURABLE_CAP_BYTES", "int", 0, RUNTIME,
       accessors=("cylon_tpu.durable.cap_bytes",),
       help="Size cap for the durable journal root: past it, whole runs "
            "are evicted least-recently-used first (spills before the "
            "manifest, so a half-evicted run re-executes instead of "
            "serving a torn journal).  Shared by the serving layer's "
            "result cache.  0 (default) = unbounded (pre-PR-7 "
            "behavior)."),
    _K("CYLON_TPU_DURABLE_RF", "int", 2, RUNTIME,
       accessors=("cylon_tpu.durable.replication_factor",),
       help="Target copies of every completed journal run across the "
            "fleet's DISTINCT journal roots (anti-entropy replication: "
            "replicas advertise per-run digests on heartbeats, the "
            "coordinator hints under-replicated runs back, replicas "
            "pull them spills-first/manifest-last).  gc_journal never "
            "evicts a run while fewer than this many roots hold it.  "
            "1 disables replication entirely (PR-19 single-root "
            "behavior, byte-identical)."),
    _K("CYLON_TPU_SCRUB_S", "float", 0.0, RUNTIME,
       accessors=("cylon_tpu.durable.scrub_interval_s",),
       help="Seconds between background journal-integrity scrub passes "
            "(re-verify every committed spill's sha256 under the GC "
            "lease; repair from a peer when one holds a good copy, "
            "quarantine manifest-LAST otherwise).  0 (default) disables "
            "the scrubber thread — corruption is then caught lazily at "
            "load time."),
    _K("CYLON_TPU_SERVE_QUEUE_CAP", "int", 64, RUNTIME,
       accessors=("cylon_tpu.serve.service.queue_cap",),
       help="Bounded admission queue of the multi-tenant query service: "
            "submissions past this depth are shed with "
            "Code.ResourceExhausted + a retry-after hint, never an "
            "unbounded wait."),
    _K("CYLON_TPU_SERVE_TENANT_SHARE", "float", 0.5, RUNTIME,
       accessors=("cylon_tpu.serve.service.tenant_share",),
       help="Largest fraction of the admission queue one tenant may "
            "occupy (flood isolation): beyond ceil(cap * share) queued "
            "requests the TENANT is shed while others keep admitting."),
    _K("CYLON_TPU_SERVE_HBM_BUDGET_BYTES", "int", 0, RUNTIME,
       accessors=("cylon_tpu.serve.service.hbm_budget_bytes",),
       help="Per-tenant HBM admission budget: a request whose input-size "
            "estimate (plus the live hbm.live_bytes watermark) exceeds "
            "it is shed with Code.ResourceExhausted at admission, "
            "before any device allocation.  0 (default) disables."),
    _K("CYLON_TPU_SERVE_DEADLINE_S", "float", 0.0, RUNTIME,
       accessors=("cylon_tpu.serve.service.default_deadline_s",),
       help="Default per-REQUEST wall-clock budget in the query service "
            "(per-tenant overridable): the Code.Timeout watchdog arms "
            "over the whole run and the scheduler stops it at the next "
            "pass boundary.  0 (default) disables."),
    _K("CYLON_TPU_SERVE_QUARANTINE_AFTER", "int", 3, RUNTIME,
       accessors=("cylon_tpu.serve.service.tenant_quarantine_after",),
       help="Per-TENANT quarantine: a tenant whose requests fail this "
            "many consecutive times is shed (Code.Unavailable + "
            "retry-after) for CYLON_TPU_SERVE_QUARANTINE_S, so one "
            "poison tenant cannot starve the rest.  0 disables."),
    _K("CYLON_TPU_SERVE_QUARANTINE_S", "float", 30.0, RUNTIME,
       accessors=("cylon_tpu.serve.service.tenant_quarantine_s",),
       help="How long a quarantined tenant stays shed before its failure "
            "streak resets."),
    _K("CYLON_TPU_QUARANTINE_AFTER", "int", 0, RUNTIME,
       accessors=("cylon_tpu.durable.quarantine_after",),
       help="Poison-pass quarantine: a part failing with the same "
            "classified code this many consecutive times is isolated "
            "into the run report (stats['quarantined']) instead of "
            "wedging retries/refinement forever.  0 (default) disables "
            "(PR-1 fail-fast behavior)."),
    _K("CYLON_TPU_ELASTIC", "bool", False, RUNTIME,
       accessors=("cylon_tpu.elastic.elastic_enabled",),
       help="Env-driven elastic opt-in: with this set (and "
            "CYLON_TPU_ELASTIC_COORD pointing at the coordinator) every "
            "distributed CylonContext joins the membership gang at its "
            "process id — the deployment path where hosts only get env "
            "vars.  ElasticConfig contexts join explicitly regardless.  "
            "Off (default) preserves the fixed-world behavior."),
    _K("CYLON_TPU_ELASTIC_COORD", "str", "", RUNTIME,
       accessors=("cylon_tpu.elastic.coordinator_address",),
       help="Elastic coordinator address (host:port) agents join; empty "
            "means no coordinator is configured (elastic contexts refuse "
            "to start)."),
    _K("CYLON_TPU_HEARTBEAT_S", "float", 0.5, RUNTIME,
       accessors=("cylon_tpu.elastic.heartbeat_interval",),
       help="Elastic agent heartbeat cadence in seconds (also the "
            "rendezvous-barrier poll interval)."),
    _K("CYLON_TPU_HEARTBEAT_TIMEOUT_S", "float", 2.5, RUNTIME,
       accessors=("cylon_tpu.elastic.heartbeat_timeout",),
       help="Silence window after which the coordinator declares a rank "
            "dead and bumps the membership epoch (shrink-and-resume).  "
            "Must exceed CYLON_TPU_HEARTBEAT_S — agents refuse to start "
            "under a pair that would instantly fence every rank."),
    _K("CYLON_TPU_COORD_DIR", "str", "", RUNTIME,
       accessors=("cylon_tpu.elastic.coord_dir",),
       help="Durable coordinator state root: the membership ledger, "
            "epoch counter, incarnation number, fence set, rendezvous "
            "latches and skew ledger are journaled to an fsync'd "
            "append-only COORD_LOG.jsonl (torn-tail tolerant), so a "
            "restarted coordinator recovers its ledger, bumps its "
            "incarnation, and bumps the epoch once — survivors resume "
            "instead of dying.  Empty (default) disables coordinator "
            "durability (a restart then has nothing to recover)."),
    _K("CYLON_TPU_COORD_RECONNECT_S", "float", 10.0, RUNTIME,
       accessors=("cylon_tpu.elastic.reconnect_window_s",),
       help="Bounded coordinator-reconnect window: after 3 failed "
            "control round trips an agent keeps re-joining under seeded "
            "full-jitter backoff for this many seconds — in-flight "
            "local passes keep executing and journaling, only "
            "membership changes stall — before CoordinatorLost fires "
            "(classified, Code.Unavailable).  0 reproduces the PR-6 "
            "fail-after-3-missed-ticks behavior exactly."),
    _K("CYLON_TPU_ROUTER_CACHE_AFFINITY", "bool", True, RUNTIME,
       accessors=("cylon_tpu.router.service.cache_affinity_enabled",),
       help="Fleet query router: steer a repeated request fingerprint to "
            "the replica that last served it, so the plan/journal caches "
            "it warmed are reused (any replica can still replay the run "
            "from the shared CYLON_TPU_DURABLE_DIR journal — affinity is "
            "a latency optimization, never a correctness requirement).  "
            "Off falls back to pure tenant-affinity + least-load "
            "placement."),
    _K("CYLON_TPU_ROUTER_POLL_S", "float", 0.05, RUNTIME,
       accessors=("cylon_tpu.router.service.poll_interval_s",),
       help="Router-side cadence for polling a proxied request's state "
            "on its replica (each poll is one small control verb; the "
            "first poll is immediate so journal cache hits return in "
            "one round trip)."),
    _K("CYLON_TPU_ROUTER_RPC_TIMEOUT_S", "float", 5.0, RUNTIME,
       accessors=("cylon_tpu.router.service.rpc_timeout_s",),
       help="Socket timeout for one router->replica proxy verb (submit/"
            "poll/cancel).  Distinct from the request's own deadline: a "
            "slow QUERY keeps polling; a slow VERB counts toward the "
            "replica-death detection that triggers re-routing."),
    _K("CYLON_TPU_ROUTER_TIMEOUT_S", "float", 600.0, RUNTIME,
       accessors=("cylon_tpu.router.service.route_timeout_s",),
       help="Absolute per-request bound at the router when the caller "
            "supplied neither timeout_s nor deadline_s: past it the "
            "router cancels the proxied ticket and answers a classified "
            "Code.Timeout — a routed request can never hang even when "
            "a replica's device wedges mid-run."),
    _K("CYLON_TPU_ROUTER_MAX_LINE_BYTES", "int", 64 << 20, RUNTIME,
       accessors=("cylon_tpu.router.service.router_max_line",),
       help="Wire cap for one router/replica data-plane message (the "
            "route verb and the submit/poll proxy carry whole encoded "
            "tables, unlike the 1 MiB control-plane default).  A single "
            "request larger than this is rejected with a classified "
            "SerializationError, never silently truncated."),
    _K("CYLON_TPU_ROUTER_HEDGE_MS", "float", 0.0, RUNTIME,
       accessors=("cylon_tpu.router.service.hedge_floor_ms",),
       help="Hedged requests: milliseconds after the primary submit "
            "before the router speculatively re-places an in-flight "
            "request on a second replica (the floor under the per-"
            "fingerprint asymmetric-EWMA p99 delay; first terminal "
            "ticket wins, the loser is proxy-cancelled at a pass "
            "boundary).  Safe only because journaled built-in ops are "
            "fingerprint-idempotent and bit-identical across replicas; "
            "custom register_op handlers hedge only when registered "
            "with idempotent=True.  0 (default) disables hedging."),
    _K("CYLON_TPU_ROUTER_BREAKER_FAILURES", "int", 3, RUNTIME,
       accessors=("cylon_tpu.router.service.breaker_failures",),
       help="Replica health breakers: consecutive classified failures "
            "(Timeout/Unavailable/UnknownError, a lost hedge race, or "
            "sustained p99 inflation) before a replica's breaker OPENs "
            "and placement skips it.  Composes with — never overrides "
            "— fencing/affinity/saturation.  0 disables the breakers."),
    _K("CYLON_TPU_ROUTER_BREAKER_COOLDOWN_S", "float", 5.0, RUNTIME,
       accessors=("cylon_tpu.router.service.breaker_cooldown_s",),
       help="Seconds an OPEN replica breaker holds before HALF_OPEN "
            "admits exactly one real request as a health probe: a "
            "clean probe re-CLOSEs the breaker, a failed (or "
            "hedge-beaten) probe re-OPENs it for another cooldown."),
    _K("CYLON_TPU_DURABLE_QUOTA_BYTES", "int", 0, RUNTIME,
       accessors=("cylon_tpu.durable.quota_bytes",),
       help="Hard disk budget for new journal spills under the shared "
            "CYLON_TPU_DURABLE_DIR: a spill that would push the root "
            "past it (or a write hitting real ENOSPC) classifies "
            "Code.ResourceExhausted and the run degrades to journal-"
            "off execution — the answer is still served (counted "
            "durable.degraded), the query never fails for disk.  "
            "Unlike CYLON_TPU_DURABLE_CAP_BYTES (GC target after the "
            "fact), the quota refuses the write up front.  0 (default) "
            "disables."),
    _K("CYLON_TPU_PROFILE", "bool", False, RUNTIME,
       accessors=("cylon_tpu.plan.profile.profiler_enabled",),
       help="Query profiler: collect per-plan-node actuals (rows, self "
            "time, exchange bytes, per-shard skew, cache hits) on every "
            "plan.execute and export a plan_profile artifact beside the "
            "traces (tools/trace_report.py --plan).  explain(analyze="
            "True) forces one profiled run regardless.  Host-side only: "
            "traced programs, cache keys and budget goldens are "
            "identical either way; off (default) is the exact "
            "pre-profiler code path."),
    _K("CYLON_TPU_STATS_DIR", "str", "", RUNTIME,
       accessors=("cylon_tpu.obs.stats_catalog.stats_dir",
                  "cylon_tpu.obs.stats_catalog.enabled"),
       help="Persistent statistics catalog root: profiled plan runs "
            "append their observed per-scan column cardinality, join-"
            "key selectivity and partition skew to an fsync'd "
            "STATS.jsonl keyed by the plan content fingerprint, "
            "reloadable across processes (optimizer.lookup_stats; "
            "advisory-only — plans are bit-identical with or without "
            "the catalog).  Empty (default) disables."),
    _K("CYLON_TPU_STATS_CAP", "int", 256, RUNTIME,
       accessors=("cylon_tpu.obs.stats_catalog.stats_cap",),
       help="Distinct plan fingerprints the statistics catalog keeps: "
            "past it STATS.jsonl compacts (atomic rewrite) to the most "
            "recently written entries."),
    _K("CYLON_TPU_METRICS_PORT", "int", 0, RUNTIME,
       accessors=("cylon_tpu.obs.openmetrics.metrics_port",),
       help="Per-process OpenMetrics scrape port: a tiny stdlib HTTP "
            "listener answers GET /metrics with the obs.metrics "
            "snapshot in Prometheus text exposition format (counters, "
            "gauges, cumulative le-bucket histograms), started when the "
            "first CylonContext initializes.  0 (default) disables; a "
            "failed bind warns and skips, never fails the context."),
    _K("CYLON_TPU_DEBUG", "bool", False, RUNTIME,
       help="Log every span's duration at INFO (cylon_tpu.obs.spans; the "
            "utils.timing shim's historical switch)."),
    _K("CYLON_TPU_TRACE", "enum", "auto", RUNTIME,
       choices=("1", "on", "auto", "0", "off"),
       accessors=("cylon_tpu.obs.spans.mode",
                  "cylon_tpu.obs.spans.enabled",
                  "cylon_tpu.obs.spans.events_enabled"),
       help="Observability tracing mode: auto keeps only the always-on "
            "aggregate stopwatch; 1/on also buffers structured events for "
            "Perfetto export (obs.export); 0/off disables spans entirely "
            "(alloc-free no-op).  In every mode but off a span is also a "
            "jax.profiler.TraceAnnotation.  Spans inside traced bodies consult it "
            "while tracing but never alter the traced computation, so no "
            "cache-key participation — the trace-time child spans appear "
            "on plan BUILDS, not on cached re-runs."),
    _K("CYLON_TPU_TRACE_DIR", "str", "traces", RUNTIME,
       accessors=("cylon_tpu.obs.export.trace_dir",),
       help="Directory for exported trace/metrics artifacts "
            "(per-rank file naming: trace.r{rank}.json)."),
    _K("CYLON_TPU_TRACE_BUFFER_CAP", "int", 65536, RUNTIME,
       accessors=("cylon_tpu.obs.spans.buffer_cap",),
       help="Maximum buffered span events per process; past it new events "
            "are dropped and counted (obs.spans.dropped), never grown."),
    _K("CYLON_TPU_TRACE_TAIL_MS", "float", 0.0, RUNTIME,
       accessors=("cylon_tpu.obs.tracectx.tail_threshold_ms",),
       help="Tail-based trace retention: a closing serve request KEEPS "
            "its buffered span events only when it was slow (latency "
            "above this many milliseconds, or above the rolling p99 "
            "estimate), failed, or head-sampled "
            "(CYLON_TPU_TRACE_SAMPLE_N); fast-and-healthy requests keep "
            "only the aggregate stopwatch — their events are discarded "
            "at request close and counted in trace.tail_dropped.  "
            "0 (default) disables retention: every buffered event is "
            "kept (the pre-PR-13 behavior)."),
    _K("CYLON_TPU_TRACE_SAMPLE_N", "int", 0, RUNTIME,
       accessors=("cylon_tpu.obs.tracectx.head_sample_n",),
       help="1-in-N head sampling for causal request traces: every Nth "
            "trace the serve front door mints is marked sampled and "
            "survives tail-based retention regardless of latency.  "
            "0 (default) disables head sampling."),
    _K("CYLON_TPU_TRACEPARENT", "str", "", RUNTIME,
       accessors=("cylon_tpu.obs.tracectx.current",),
       help="Ambient W3C traceparent (00-<32 hex trace>-<16 hex span>-"
            "<2 hex flags>) adopted as this process's root trace context "
            "whenever no request-scoped context is active — the "
            "deployment hook for rooting a whole worker process in a "
            "caller's trace (the CI tracing smoke roots rank 0 with it; "
            "peers join causally via barrier propagation).  Empty "
            "(default) leaves spans unstamped outside active requests."),
    _K("CYLON_TPU_RUN_ID", "str", "", RUNTIME,
       accessors=("cylon_tpu.obs.fleet.current_run_id",),
       help="Logical run id namespacing trace/metrics exports "
            "(trace.<run_id>.r<rank>.json) and flight-recorder dumps, so "
            "back-to-back runs sharing CYLON_TPU_TRACE_DIR never clobber.  "
            "elastic_run installs its own run_id when this is unset; empty "
            "(default) keeps the flat per-rank naming."),
    _K("CYLON_TPU_FLIGHT_RING_CAP", "int", 512, RUNTIME,
       accessors=("cylon_tpu.obs.spans.ring_cap",
                  "cylon_tpu.obs.fleet.flight_enabled"),
       help="Always-on flight-recorder ring: the most recent N span/"
            "instant events are kept even when CYLON_TPU_TRACE=1 event "
            "buffering is off, and auto-dumped with a metrics snapshot to "
            "CYLON_TPU_TRACE_DIR/flight/<run_id>.r<rank>.json on any "
            "classified terminal event (quarantine, shed, rank loss, "
            "straggler fencing, fatal pass failure) — post-mortems never "
            "depend on pre-armed tracing.  0 disables the ring and the "
            "recorder."),
    _K("CYLON_TPU_CLOCK_SYNC_N", "int", 8, RUNTIME,
       accessors=("cylon_tpu.elastic.clock_sync_rounds",),
       help="Round trips per clock-alignment handshake when an elastic "
            "agent joins: NTP-style best-of-N offset/uncertainty against "
            "the coordinator clock (tools/trace_merge.py aligns per-rank "
            "traces with it), refined one round per heartbeat."),
    _K("CYLON_TPU_FAULT_DELAY_S", "float", 0.25, RUNTIME,
       accessors=("cylon_tpu.resilience.fault_delay_s",),
       help="Sleep injected by the `delay` fault kind (a seeded straggler "
            "for skew-attribution tests: the process keeps heartbeating "
            "but arrives late at every collective)."),
    _K("CYLON_TPU_LOCK_RECORD", "bool", False, RUNTIME,
       accessors=("cylon_tpu.analysis.locks.record_enabled",),
       help="Enable the runtime lock-acquisition recorder (cylint Level 3): "
            "threading.Lock/RLock/Condition factories are wrapped so every "
            "held->acquired edge is captured and checked against the "
            "committed lock-order golden.  Test/CI instrumentation only; "
            "never enabled in production paths."),
    _K("CYLON_TEST_NO_COMPILE_CACHE", "bool", False, RUNTIME,
       help="Disable the per-backend persistent XLA compile cache.  Read "
            "directly in utils/compile_cache.py (the enabler must work "
            "before the package is importable); listed here for the "
            "reference table only."),
]}

_FALSE_WORDS = ("0", "false", "off", "no")


def knob_raw(name: str) -> Optional[str]:
    """The knob's raw environment value, or None when unset.  ``name`` must
    be a registered knob — an unregistered read is exactly the drift this
    registry exists to prevent."""
    if name not in KNOBS:
        raise KeyError(f"unregistered knob {name!r}; add it to "
                       f"cylon_tpu.config.KNOBS")
    return os.environ.get(name)


def knob(name: str):
    """The knob's parsed value: environment override when set and valid,
    else the registered default.  Parse failures (bad int/float, enum value
    outside ``choices``) fall back to the default — matching the historical
    per-site ``except ValueError`` behavior."""
    k = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return k.default
    if k.kind == "str":
        return raw
    if k.kind == "enum":
        return raw if raw in k.choices else k.default
    if k.kind == "bool":
        return raw.lower() not in _FALSE_WORDS
    if k.kind == "int":
        try:
            return int(raw)
        except ValueError:
            return k.default
    if k.kind == "float":
        try:
            return float(raw)
        except ValueError:
            return k.default
    raise AssertionError(f"unknown knob kind {k.kind!r}")


def trace_knobs() -> Tuple[Knob, ...]:
    """Registry rows with trace scope, in declaration order."""
    return tuple(k for k in KNOBS.values() if k.scope == TRACE)


def trace_cache_token() -> Tuple[Tuple[str, Optional[str]], ...]:
    """The (name, raw value) vector of every cache-key trace-scope knob.

    Jit-plan caches append this token to their keys so that flipping ANY
    trace-time knob retraces instead of serving a program traced under the
    other realization — the generalization of PR 2's hand-keyed
    ``CYLON_TPU_SHUFFLE_PACK`` fix to the whole registry.  Raw values (not
    parsed/backend-resolved) suffice: the backend is fixed per process, so
    "auto" resolves identically for the cache's lifetime."""
    return tuple((k.name, os.environ.get(k.name))
                 for k in KNOBS.values() if k.cache_key)


@contextlib.contextmanager
def knob_env(**overrides: Optional[str]):
    """Temporarily set (or, with None, unset) registered knobs in the
    process environment — the sanctioned way for harness code (benches,
    the budget tracer, tests) to flip knobs without reaching into
    ``os.environ`` and tripping cylint's CY102."""
    for name in overrides:
        if name not in KNOBS:
            raise KeyError(f"unregistered knob {name!r}")
    saved = {name: os.environ.get(name) for name in overrides}
    try:
        for name, val in overrides.items():
            if val is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = val
        yield
    finally:
        for name, val in saved.items():
            if val is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = val


def knob_table() -> str:
    """The registry rendered as a markdown table (README's authoritative
    ``CYLON_TPU_*`` reference; ``python -m cylon_tpu.analysis --knobs``)."""
    rows = ["| knob | type | default | scope | cache key | purpose |",
            "|---|---|---|---|---|---|"]
    for k in KNOBS.values():
        kind = f"enum{list(k.choices)}" if k.kind == "enum" else k.kind
        rows.append(f"| `{k.name}` | {kind} | `{k.default!r}` | {k.scope} "
                    f"| {'yes' if k.cache_key else 'no'} | {k.help} |")
    return "\n".join(rows)
