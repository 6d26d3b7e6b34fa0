"""Plan executor: lowers an (optimized) physical plan onto the engine.

Two lowering modes share one interpreter, so the A/B is exact:

- ``CYLON_TPU_PLAN`` off — the EAGER plan: no pruning, every
  distributed join/group-by pays its full shuffle, every intermediate
  materializes (bit-identical to the ``Table`` method chain by
  construction: the same ``_local_join`` / ``distributed_groupby`` /
  shuffle code paths run in the same order);
- on (default) — the optimized plan: pruned scans, elided/shared
  exchanges, and the fused join→aggregate shard body.

Bit-identity between the two modes is a hard invariant (asserted by
tests and the full-tree smoke): elision never changes which rows meet,
only where; the fused body runs the same kernels in the same order on
the same values; and an elided group-by's final combine folds exactly
one partial per group (co-location guarantees it), which is the
identity for every combine op.

A stage the executor builds itself -- a filter with its compaction, a
derive, the fused join's count pass, the fused join -> chain ->
aggregate -- is ONE cached program (``plan_filter``, ``plan_derive``,
``plan_join_count``, ``plan_fused``) on one shard as on many
(``_Executor._stage``), keyed by the shape of its expressions and taking
their literals as operands: a predicate with a date nobody sent before
builds no program.

Durable/serve integration is at PLAN granularity: one fingerprint for
the whole op chain (``LogicalPlan.fingerprint``), one journaled result
frame — a repeated plan replays from spill with zero compiles and zero
device passes (``plan.cache_hit``; serve op ``"plan"``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import config, durable
from ..obs import fleet as obs_fleet
from ..obs import metrics as obs_metrics
from ..obs import spans as obs_spans
from ..obs import stats_catalog
from ..status import Code, CylonError, Status
from . import expr as expr_mod
from . import ir, optimizer
from . import profile as profile_mod


def planner_enabled() -> bool:
    """Whether plan.execute() runs the optimizer (``CYLON_TPU_PLAN``;
    auto/on = optimize, off = eager per-op lowering).  A host-side
    plan-build choice like CYLON_TPU_SHUFFLE: each mode builds
    differently-keyed stage programs, so no cache-key participation."""
    return str(config.knob("CYLON_TPU_PLAN")) not in ("0", "off")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def execute(plan: "ir.LogicalPlan", ctx=None, pass_guard=None,
            stats_out: Optional[dict] = None,
            profile: Optional["profile_mod.PlanProfile"] = None):
    """Run the plan, returning a Table.  With ``CYLON_TPU_DURABLE_DIR``
    set the run is journaled at plan granularity; a repeated fingerprint
    is served entirely from spill (a LOCAL 1-shard table — zero
    compiles, zero device passes).

    ``profile=`` (or the ``CYLON_TPU_PROFILE`` knob) collects per-node
    actuals into a :class:`~cylon_tpu.plan.profile.PlanProfile` — the
    EXPLAIN ANALYZE substrate — and, with ``CYLON_TPU_STATS_DIR`` set,
    persists the observed statistics to the catalog under the plan
    fingerprint.  All host-side: the traced programs and their cache
    keys are identical with the profiler on or off."""
    from ..table import Table

    ctx = ctx if ctx is not None else plan._ctx()
    if ctx is None:
        from ..context import default_context

        ctx = default_context()
    world = plan._world()
    enabled = planner_enabled()
    stats = stats_out if stats_out is not None else {}
    stats.update(passes=1, passes_skipped=0, parts_run=0)
    prof = profile
    if prof is None and profile_mod.profiler_enabled():
        prof = profile_mod.PlanProfile()

    fp: Optional[str] = None
    sfp: Optional[str] = None
    journal = None
    if durable.enabled() or (prof is not None and stats_catalog.enabled()):
        fp = plan.fingerprint()
    if prof is not None and fp is not None and stats_catalog.enabled():
        # the CATALOG is keyed by the strategy-independent base
        # fingerprint: observations must describe what the query IS, not
        # what the planner chose, or a strategy flip would orphan the
        # very statistics that justified it.  With the adaptive knob off
        # the full fingerprint IS the base one (no strategies to fold),
        # so the second content hash is skipped.
        sfp = (plan.base_fingerprint() if optimizer.planner_adaptive()
               else fp)
    if prof is not None:
        prof.fingerprint = fp
        if sfp is not None:
            prof.estimates = stats_catalog.lookup(sfp)
    if durable.enabled():
        journal = durable.open_run(fp, "plan", world=world)
        if journal is not None and journal.is_complete():
            got = journal.load_pass(0, 0)
            if got is not None:
                frame, rows = got
                obs_metrics.counter_add("plan.cache_hit")
                obs_spans.instant("plan.cache_hit", fingerprint=fp[:12],
                                  rows=rows)
                stats.update(passes_skipped=1, rows=rows, cache_hit=True)
                if prof is not None:
                    prof.plan_cache_hit = True
                    prof.finalize(optimizer.optimize(plan, enabled=enabled),
                                  0)
                    prof.export()
                from ..context import CylonContext

                return Table.from_numpy(list(frame), list(frame.values()),
                                        ctx=CylonContext.Init())

    t_run0 = time.perf_counter_ns()
    try:
        with obs_spans.span("plan.optimize", world=world, enabled=enabled):
            phys = optimizer.optimize(plan, enabled=enabled)
        if enabled:
            obs_metrics.counter_add("plan.shuffles_elided",
                                    phys.shuffles_elided)
            obs_metrics.counter_add("plan.columns_pruned",
                                    phys.columns_pruned)
        if phys.adaptive:
            obs_metrics.counter_add("plan.broadcast_joins",
                                    phys.broadcast_joins)
            obs_metrics.counter_add("plan.keys_salted",
                                    phys.keys_salted)
        with obs_spans.span("plan.execute", world=world, nodes=phys.nodes,
                            elided=phys.shuffles_elided,
                            pruned=phys.columns_pruned, optimized=enabled):
            result = _Executor(plan, phys, ctx, pass_guard, prof).run()
    except Exception as e:
        # planner-path terminal failure: dump the flight recorder like
        # exec/serve/elastic terminal events already do, so the
        # post-mortem exists even when tracing was never armed.  NOT
        # terminal: a pass_guard's EpochMismatch is an ordinary elastic
        # resume (elastic_run catches it and re-derives), and Cancelled
        # is a deliberate caller action — dumping "plan_fatal" for
        # those would litter every membership change / cancel with
        # misleading fatal post-mortems (exec.py's fatal() draws the
        # same line)
        st = Status.from_exception(e)
        if st.code not in (Code.EpochMismatch, Code.Cancelled):
            # terminal instant + flight dump, both stamped with the
            # active request trace (the instant via the ambient context,
            # the dump via flight_record's trace capture), so the
            # post-mortem joins to the request that died here
            obs_spans.instant("plan.fatal", code=st.code.name,
                              fingerprint=fp[:12] if fp else None,
                              world=world)
            obs_fleet.flight_record(
                "plan_fatal", code=st.code.name,
                fingerprint=fp[:12] if fp else None, world=world,
                error=f"{type(e).__name__}: {e}"[:200])
        raise
    stats.update(parts_run=1, rows=result.row_count, cache_hit=False)
    if prof is not None:
        prof.finalize(phys, time.perf_counter_ns() - t_run0)
        prof.attach_fleet_skew(ctx)
        if sfp is not None:
            stats_catalog.record(sfp, prof.catalog_record(plan))
        prof.export()

    if journal is not None:
        frame = result.to_numpy()
        journal.record_pass(0, 0, frame, int(stats["rows"]))
        journal.record_done(1, int(stats["rows"]))
        durable.gc_journal()
    if phys.root.part is not None:
        result._partitioning = phys.root.part
    return result


def run_service(plan: "ir.LogicalPlan", *, ctx=None, pass_guard=None,
                **_kw):
    """Serve-layer runner (op ``"plan"``): executes on the plan inputs'
    own mesh (the service ``ctx`` is accepted for signature parity) and
    returns ``(host frame, stats)`` with the journal-replay stats shape
    ``serve.cache.served_from_journal`` expects."""
    stats: dict = {}
    t = execute(plan, pass_guard=pass_guard, stats_out=stats)
    return t.to_numpy(), stats


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class _Executor:
    def __init__(self, plan, phys: optimizer.PhysPlan, ctx, pass_guard,
                 profile: Optional["profile_mod.PlanProfile"] = None):
        self.plan = plan
        self.phys = phys
        self.ctx = ctx
        self.world = phys.world
        self.pass_guard = pass_guard
        self.profile = profile

    def run(self):
        return self._exec(self.phys.root)

    def _guard(self) -> None:
        if self.pass_guard is not None:
            self.pass_guard()

    # -- generic dispatch ------------------------------------------------
    def _exec(self, p: optimizer.Phys):
        prof = self.profile
        if prof is None:
            return self._exec_node(p)
        # profiled: two clock reads + a handful of counter reads around
        # the node, plus one row-count fetch of the ALREADY-materialized
        # result — the node's subtree deltas; finalize() subtracts
        # recorded descendants for self values.  Nothing traced changes.
        before = profile_mod.counters_now()
        t0 = time.perf_counter_ns()
        t = self._exec_node(p)
        prof.record_node(p, t, time.perf_counter_ns() - t0, before)
        return t

    def _exec_node(self, p: optimizer.Phys):
        n = p.node
        if isinstance(n, ir.Scan):
            return self._project_to(self.plan.inputs[n.idx], p.keep)
        if isinstance(n, ir.Project):
            return self._project_to(self._exec(p.children[0]), p.keep)
        if isinstance(n, ir.Filter):
            return self._filter_table(self._exec(p.children[0]), n.pred,
                                      p.keep)
        if isinstance(n, ir.Derive):
            t = self._exec(p.children[0])
            if not p.ann.get("dead"):
                t = self._derive_table(t, n.name, n.value)
            return self._project_to(t, p.keep)
        if isinstance(n, ir.Join):
            return self._project_to(self._exec_join(p), p.keep)
        if isinstance(n, ir.Aggregate):
            if p.ann.get("fuse"):
                return self._project_to(self._fused_join_agg(p), p.keep)
            return self._project_to(self._exec_agg(p), p.keep)
        if isinstance(n, ir.Sort):
            return self._project_to(self._exec_sort(p), p.keep)
        if isinstance(n, ir.Limit):
            return self._project_to(self._exec_limit(p), p.keep)
        raise CylonError(Code.Invalid, f"unknown node {n.kind!r}")

    @staticmethod
    def _project_to(t, keep: Tuple[str, ...]):
        if tuple(t.names) == tuple(keep):
            return t
        return t.project(list(keep))

    # -- scans / local row ops -------------------------------------------
    def _stage(self, name: str, kind: str, fn, tables, key: tuple,
               exprs=(), **attrs):
        """Launch one stage program: ``fn(*tables, *operands)`` as the
        cached program ``name`` (``table._shard_wise``), keyed by ``key``
        and the shapes of ``exprs``, whose literals it takes as operands.
        Span ``plan.stage`` of ``kind`` carries the program's name;
        counters ``plan.stage_programs`` and ``plan.literal_operands``."""
        from ..table import _shard_wise

        operands = expr_mod.host_operands(exprs)
        obs_metrics.counter_add("plan.stage_programs")
        obs_metrics.counter_add("plan.literal_operands", len(operands))
        with obs_spans.span("plan.stage", kind=kind, program=name, **attrs):
            return _shard_wise(tables[0].ctx, fn, *tables,
                               key=(name, key,
                                    tuple(e.shape() for e in exprs)),
                               name=name, operands=operands)

    def _filter_table(self, t, pred, keep: Optional[Tuple[str, ...]] = None):
        """The rows of ``t`` that ``pred`` keeps, compacted, as columns
        ``keep`` (all of them where it is None): one program, so a column
        the predicate alone reads never goes through the compaction."""
        import jax.numpy as jnp

        from ..ops import compact as compact_mod
        from ..ops import keys as keys_mod
        from ..table import Table

        names, ctx = t.names, t.ctx
        keep = names if keep is None else tuple(keep)

        def fn(tt, *lits):
            cap = tt.columns[0].data.shape[0]
            env = dict(zip(names, tt.columns))
            c = pred.evaluate(env, lits)
            mask = c.data & c.validity & compact_mod.live_mask(
                cap, tt.row_counts[0])
            cols, m = keys_mod.compact_columns(mask, [env[n] for n in keep])
            return Table(cols, jnp.reshape(m, (1,)), keep, ctx)

        return self._stage("plan_filter", "filter", fn, (t,),
                           (names, keep), (pred,))

    def _derive_table(self, t, name: str, value):
        from ..table import Table

        names, ctx = t.names, t.ctx
        out_names = names + (name,)

        def fn(tt, *lits):
            env = dict(zip(names, tt.columns))
            c = value.evaluate(env, lits)
            return Table(tt.columns + (c,), tt.row_counts, out_names, ctx)

        return self._stage("plan_derive", "derive", fn, (t,),
                           (names, name), (value,))

    def _exec_chain(self, p: optimizer.Phys, keep: Tuple[str, ...]):
        """Execute a pure scan chain with an overridden column set (the
        shared-scan rule's union keep).  Profiled like ``_exec`` — a
        self-join CSE'd by the shared-scan rule must still feed scan
        cardinality and filter selectivity to the catalog (the chain
        runs ONCE for both sides, so records land on the LEFT child's
        subtree; the right twin stays unannotated)."""
        prof = self.profile
        if prof is None:
            return self._exec_chain_node(p, keep)
        before = profile_mod.counters_now()
        t0 = time.perf_counter_ns()
        t = self._exec_chain_node(p, keep)
        if p.nid not in prof.nodes:
            prof.record_node(p, t, time.perf_counter_ns() - t0, before)
        return t

    def _exec_chain_node(self, p: optimizer.Phys, keep: Tuple[str, ...]):
        n = p.node
        if isinstance(n, ir.Scan):
            t = self.plan.inputs[n.idx]
            want = set(keep)
            return t.project([c for c in t.names if c in want])
        child = p.children[0]
        if isinstance(n, ir.Project):
            return self._exec_chain(child, keep)
        if isinstance(n, ir.Filter):
            below = tuple(dict.fromkeys(tuple(keep)
                                        + tuple(sorted(n.pred.columns()))))
            t = self._exec_chain(child, below)
            return self._filter_table(t, n.pred, tuple(
                c for c in t.names if c in set(keep)))
        if isinstance(n, ir.Derive):
            below = tuple(dict.fromkeys(
                tuple(c for c in keep if c != n.name)
                + tuple(sorted(n.value.columns()))))
            t = self._exec_chain(child, below)
            if n.name in set(keep):
                t = self._derive_table(t, n.name, n.value)
            return self._project_to(t, tuple(c for c in t.names
                                             if c in set(keep)))
        raise AssertionError(n.kind)

    # -- shuffles ---------------------------------------------------------
    def _shuffle(self, t, keys: Tuple[str, ...], side: str):
        from ..parallel import ops as par_ops

        self._guard()
        idx = tuple(t.names.index(k) for k in keys)
        with obs_spans.span("plan.stage", kind="shuffle", side=side,
                            keys=len(idx), columns=len(t.names)):
            return par_ops.shuffle(t, idx)

    def _note_elided(self, side: str, keys: Tuple[str, ...]) -> None:
        obs_spans.instant("plan.shuffle_elided", side=side,
                          keys=",".join(keys))

    def _broadcast(self, t, side: str, p: optimizer.Phys):
        from ..parallel import ops as par_ops

        self._guard()
        est = p.ann.get("broadcast") or {}
        with obs_spans.span("plan.stage", kind="broadcast", side=side,
                            columns=len(t.names),
                            est_bytes=est.get("bytes"),
                            source=est.get("source")):
            return par_ops.broadcast_gather(t)

    def _join_inputs(self, p: optimizer.Phys):
        node: ir.Join = p.node  # type: ignore[assignment]
        lc, rc = p.children
        if p.ann.get("shared"):
            union = tuple(dict.fromkeys(tuple(lc.keep) + tuple(rc.keep)))
            base = self._exec_chain(lc, union)
            shuffled = self._shuffle(base, p.ann["left"][1], side="shared")
            self._note_elided("shared", p.ann["right"][1])
            lt = self._project_to(shuffled, lc.keep)
            rt = self._project_to(shuffled, rc.keep)
            return lt, rt
        lt = self._exec(lc)
        rt = self._exec(rc)
        la, ra = p.ann.get("left", ("local",)), p.ann.get("right",
                                                          ("local",))
        if la[0] == "shuffle":
            lt = self._shuffle(lt, la[1], side="left")
        elif la[0] == "elide":
            self._note_elided("left", la[1])
        elif la[0] == "broadcast":
            lt = self._broadcast(lt, "left", p)
        if ra[0] == "shuffle":
            rt = self._shuffle(rt, ra[1], side="right")
        elif ra[0] == "elide":
            self._note_elided("right", ra[1])
        elif ra[0] == "broadcast":
            rt = self._broadcast(rt, "right", p)
        # ("keep", keys): the broadcast join's probe side stays exactly
        # where it is — zero bytes moved
        return lt, rt

    def _join_cfg(self, node: ir.Join, lt, rt):
        from ..config import JoinConfig

        cfg = JoinConfig.of(node.how, node.algorithm,
                            tuple(lt.names.index(k) for k in node.left_on),
                            tuple(rt.names.index(k) for k in node.right_on),
                            node.left_prefix, node.right_prefix)
        from ..table import _check_join_keys

        return _check_join_keys(lt, rt, cfg)

    def _exec_join(self, p: optimizer.Phys):
        from ..table import _local_join

        node: ir.Join = p.node  # type: ignore[assignment]
        lc, rc = p.children
        lt, rt = self._join_inputs(p)
        cfg = self._join_cfg(node, lt, rt)
        self._guard()
        with obs_spans.span("plan.stage", kind="join", how=node.how,
                            algorithm=node.algorithm):
            joined = _local_join(lt, rt, cfg)
        # rename the pruned physical output to the LOGICAL names (the
        # collision set of the full schemas, not the pruned ones)
        logical = tuple(node.out_name("left", n) for n in lc.keep) \
            + tuple(node.out_name("right", n) for n in rc.keep)
        return joined.rename(list(logical))

    # -- aggregates -------------------------------------------------------
    def _agg_spec(self, node: ir.Aggregate, names: Tuple[str, ...]):
        by_idx = tuple(names.index(n) for n in node.by)
        aggs = tuple((names.index(n), op) for n, op in node.aggs)
        return by_idx, aggs

    def _exec_agg(self, p: optimizer.Phys):
        from ..parallel import ops as par_ops
        from ..table import _local_groupby

        node: ir.Aggregate = p.node  # type: ignore[assignment]
        t = self._exec(p.children[0])
        by_idx, aggs = self._agg_spec(node, tuple(t.names))
        mode = p.ann.get("mode", "eager")
        self._guard()
        with obs_spans.span("plan.stage", kind="aggregate", mode=mode,
                            keys=len(by_idx), aggs=len(aggs)):
            if mode == "local" or t.num_shards == 1:
                out = _local_groupby(t, by_idx, aggs, node.ddof)
            elif mode == "elided":
                self._note_elided("aggregate", node.by)
                out = par_ops.distributed_groupby(t, by_idx, aggs,
                                                  node.ddof,
                                                  pre_partitioned=True)
            else:
                out = par_ops.distributed_groupby(
                    t, by_idx, aggs, node.ddof,
                    salt=int(p.ann.get("salt", 0)))
        return out.rename(list(node.names))

    def _fused_join_agg(self, p: optimizer.Phys):
        """ONE jitted shard body: join probe + chained derives/filters +
        local aggregate — the join intermediate never materializes.  An
        exact count pass sizes the join output first (a too-small
        capacity would silently truncate INSIDE the fused program, so
        the planner never reuses a stale capacity here)."""
        import jax.numpy as jnp

        from ..config import JoinAlgorithm
        from ..ops import compact as compact_mod
        from ..ops import groupby as groupby_mod
        from ..ops import join as join_mod
        from ..ops import keys as keys_mod
        from ..parallel import ops as par_ops
        from ..table import Table, _cap_round, _get_everywhere, host_sync

        node: ir.Aggregate = p.node  # type: ignore[assignment]
        jphys: optimizer.Phys = p.ann["fuse_join"]  # type: ignore
        chain: List[optimizer.Phys] = p.ann["fuse_chain"]  # type: ignore
        jnode: ir.Join = jphys.node  # type: ignore[assignment]
        lc, rc = jphys.children

        lt, rt = self._join_inputs(jphys)
        cfg = self._join_cfg(jnode, lt, rt)
        jt, algo = cfg.join_type, (
            "hash" if cfg.algorithm == JoinAlgorithm.HASH else "sort")
        join_names = tuple(jnode.out_name("left", n) for n in lc.keep) \
            + tuple(jnode.out_name("right", n) for n in rc.keep)
        ctx = lt.ctx
        mode = p.ann.get("mode", "local")
        if mode == "elided":
            self._note_elided("aggregate", node.by)

        self._guard()
        # the chain's literals are the fused program's operands: its key
        # holds what each node does, not the values it does it with
        steps = [ph for ph in chain if isinstance(ph.node, ir.Filter) or (
            isinstance(ph.node, ir.Derive) and not ph.ann.get("dead"))]
        exprs = tuple(ph.node.pred if isinstance(ph.node, ir.Filter)
                      else ph.node.value for ph in steps)
        stage_spec = (jnode.spec()[:7], node.spec()[:4], tuple(
            (ph.node.kind, getattr(ph.node, "name", None),
             bool(ph.ann.get("dead"))) for ph in chain))

        def count_fn(a, b):
            c = join_mod.join_row_count(
                a.columns, a.row_counts[0], b.columns, b.row_counts[0],
                cfg.left_on, cfg.right_on, jt, algo)
            return jnp.reshape(c, (1,))

        counts = self._stage("plan_join_count", "join_count", count_fn,
                             (lt, rt), stage_spec[:1])
        out_cap = _cap_round(max(1, int(np.max(host_sync(
            counts, "plan.join_count", _get_everywhere)))))
        if self.profile is not None:
            # the fused join never materializes, but the exact count
            # pass that sizes it IS its observed cardinality — record
            # it so join selectivity reaches the statistics catalog
            self.profile.record_fused_join(jphys, counts)

        # the aggregate's partial/final split mirrors distributed_groupby
        # exactly (bit-identity with the eager path); 1-shard worlds run
        # the requested aggs directly, matching _local_groupby
        agg_names = tuple(node.names)
        by_names, aggs_by_name = node.by, node.aggs
        ddof = node.ddof
        split = mode == "elided"
        if split:
            partial_list, partial_index = par_ops.groupby_partial_plan(
                aggs_by_name)

        def fused_fn(a: Table, b: Table, *lits) -> Table:
            operands = expr_mod.split_operands(exprs, lits)
            cols, m = join_mod.join_gather(
                a.columns, a.row_counts[0], b.columns, b.row_counts[0],
                cfg.left_on, cfg.right_on, jt, out_cap, algo)
            env = dict(zip(join_names, cols))
            count = m
            for ph, ops in reversed(list(zip(steps, operands))):
                cn = ph.node
                if isinstance(cn, ir.Derive):
                    env[cn.name] = cn.value.evaluate(env, ops)
                else:
                    cap = next(iter(env.values())).data.shape[0]
                    c = cn.pred.evaluate(env, ops)
                    keepm = c.data & c.validity & compact_mod.live_mask(
                        cap, count)
                    kept, count = keys_mod.compact_columns(
                        keepm, env.values())
                    env = dict(zip(env, kept))
                # Project, dead Derive: nothing to run, env is by name
            in_names = tuple(by_names) + tuple(n for n, _ in aggs_by_name)
            in_names = tuple(dict.fromkeys(in_names))
            in_cols = tuple(env[n] for n in in_names)
            by_idx = tuple(in_names.index(n) for n in by_names)
            nkeys = len(by_idx)
            if not split:
                aggs_i = tuple((in_names.index(n), op)
                               for n, op in aggs_by_name)
                out_cols, g = groupby_mod.hash_groupby(
                    in_cols, count, by_idx, aggs_i, ddof)
                return Table(tuple(out_cols), jnp.reshape(g, (1,)),
                             agg_names, ctx)
            partial_i = tuple((in_names.index(n), pop)
                              for n, pop in partial_list)
            pcols, pm = groupby_mod.hash_groupby(in_cols, count, by_idx,
                                                 partial_i, ddof)
            key_range = tuple(range(nkeys))
            final_aggs = tuple(
                (nkeys + i, groupby_mod.combine_op(pop))
                for i, (_, pop) in enumerate(partial_list))
            fcols, fm = groupby_mod.hash_groupby(pcols, pm, key_range,
                                                 final_aggs, ddof)
            out_cols = par_ops.finalize_groupby_columns(
                fcols, nkeys, tuple((in_names.index(n), op)
                                    for n, op in aggs_by_name),
                {(in_names.index(n), pop): i
                 for i, (n, pop) in enumerate(partial_list)}, ddof)
            return Table(tuple(out_cols), jnp.reshape(fm, (1,)),
                         agg_names, ctx)

        return self._stage("plan_fused", "fused_join_agg", fused_fn,
                           (lt, rt), (stage_spec, out_cap), exprs,
                           mode=mode, out_cap=out_cap)

    # -- sort / limit -----------------------------------------------------
    def _exec_sort(self, p: optimizer.Phys):
        from ..config import SortOptions

        node: ir.Sort = p.node  # type: ignore[assignment]
        t = self._exec(p.children[0])
        self._guard()
        opts = SortOptions(ascending=node.ascending[0],
                           nulls_first=node.nulls_first)
        with obs_spans.span("plan.stage", kind="sort",
                            keys=len(node.by)):
            return t.distributed_sort(list(node.by), options=opts,
                                      ascending=list(node.ascending))

    def _exec_limit(self, p: optimizer.Phys):
        import jax.numpy as jnp

        from ..table import Table

        node: ir.Limit = p.node  # type: ignore[assignment]
        t = self._exec(p.children[0])
        self._guard()
        with obs_spans.span("plan.stage", kind="limit", n=node.n):
            cols, total = t._gathered_columns()
            local = Table(tuple(cols), jnp.asarray([total], jnp.int32),
                          t.names, t.ctx)
            n = min(node.n, int(total))
            return local.take_rows(np.arange(n, dtype=np.int64))
