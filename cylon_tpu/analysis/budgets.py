"""Level-2 (jaxpr) collective-launch budgets.

Generalizes the one-off jaxpr assertion of tests/test_shuffle_pack.py
into a committed gate: the shuffle, task-shuffle, hash-partition and
chunked-pass entry points are traced at a small canonical shape grid and
their collective-launch counts compared against golden budget files
(``cylon_tpu/analysis/budgets/*.json``).  A future edit that silently
regresses the packed exchange from 1 data collective back to 13 (one per
buffer per column) fails tier-1 instead of waiting for TPU bench time.

Two capture modes:

- the bucketed shuffle, task shuffle and hash partition run FOR REAL on a
  world-4 virtual CPU mesh with ``parallel.ops._shard_map`` instrumented —
  the recorded jaxpr is the exact plan the entry point built, not a
  re-derivation that could drift from it;
- the ragged shuffle body is traced directly (``jax.make_jaxpr`` only —
  XLA:CPU cannot execute RaggedAllToAll), and the chunked-engine pass
  program (``hash_groupby``) is traced directly because the chunked
  engine builds it as a throwaway ``@jax.jit`` closure per level.

Counts over ``ENFORCED_PRIMS`` (the collective families) are compared
exactly; ``INFORMATIONAL_PRIMS`` (gather/scatter/sort launches) are
recorded in the goldens for trend reading but not enforced — they shift
with jax/XLA versions, collectives do not.
"""
from __future__ import annotations

import json
import os.path as _osp
from typing import Dict, List, Optional, Sequence, Tuple

from .. import config
from .astlint import Finding

#: collective primitive families whose launch counts are enforced exactly
ENFORCED_PRIMS: Tuple[str, ...] = (
    "all_to_all", "ragged_all_to_all", "all_gather", "psum", "ppermute")

#: data-movement launches recorded for trend reading, never enforced
INFORMATIONAL_PRIMS: Tuple[str, ...] = ("gather", "scatter", "sort")

BUDGET_DIR = _osp.join(_osp.dirname(_osp.abspath(__file__)), "budgets")

#: the canonical grid: small enough to trace in seconds on CPU, wide
#: enough to cover every dtype layout of the packed plane
GRID = {"world": 4, "shard_cap": 64, "columns": "i32,i64,f64,f32,bool,str8"}


def count_prims(jaxpr, names) -> int:
    """Recursively count primitive applications named in ``names`` across
    a jaxpr and every sub-jaxpr (pjit/shard_map/scan bodies).  The shared
    meter behind both this gate and tests/test_shuffle_pack.py."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += count_prims(inner, names)
    return n


def collect_counts(closed_jaxpr) -> Dict[str, Dict[str, int]]:
    """Per-primitive launch counts of one traced plan, split into the
    enforced and informational families."""
    core = closed_jaxpr.jaxpr
    return {
        "collectives": {p: count_prims(core, (p,)) for p in ENFORCED_PRIMS},
        "informational": {p: count_prims(core, (p,))
                          for p in INFORMATIONAL_PRIMS},
    }


# ---------------------------------------------------------------------------
# canonical inputs
# ---------------------------------------------------------------------------


def _mixed_frame(n: int):
    """Deterministic n-row frame covering every plane field layout:
    32-bit, 64-bit (word pairs), sub-word (bool), and strings."""
    import numpy as np

    rng = np.random.default_rng(7)
    return {
        "k32": rng.integers(0, 50, n).astype(np.int32),
        "v64": rng.integers(-(2 ** 40), 2 ** 40, n).astype(np.int64),
        "f64": rng.normal(size=n).astype(np.float64),
        "f32": rng.normal(size=n).astype(np.float32),
        "flag": (rng.integers(0, 2, n) == 1),
        "tag": np.array([f"s{i % 13:06d}" for i in range(n)]),
    }


def _canonical_table(ctx):
    from ..table import Table

    world, cap = GRID["world"], GRID["shard_cap"]
    n = world * cap
    arrs = _mixed_frame(n)
    return Table.from_numpy(list(arrs), list(arrs.values()), ctx=ctx,
                            capacity=n)


def _budget_ctx():
    """A world-4 context on the virtual CPU mesh (the test harness grid)."""
    import jax

    from ..context import CylonContext, TPUConfig

    if len(jax.devices()) < GRID["world"]:
        raise RuntimeError(
            f"budget tracing needs >= {GRID['world']} devices; launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 and "
            f"JAX_PLATFORMS=cpu (python -m cylon_tpu.analysis sets this up "
            f"when jax is not yet imported)")
    return CylonContext.InitDistributed(TPUConfig(world_size=GRID["world"]))


class _PlanRecorder:
    """Instruments ``parallel.ops._shard_map`` so the first invocation of
    each wanted plan also records ``jax.make_jaxpr`` of the exact body and
    specs the entry point built."""

    def __init__(self, wanted: Sequence[str]):
        self.wanted = set(wanted)
        self.jaxprs: Dict[str, object] = {}

    def __enter__(self):
        import jax

        from ..parallel import ops as par_ops

        self._par_ops = par_ops
        self._orig = par_ops._shard_map
        recorder = self

        def instrumented(ctx, fn, key, shapes_key, out_specs=None):
            entry = recorder._orig(ctx, fn, key, shapes_key, out_specs)
            tag = key[0] if isinstance(key, tuple) and key else None
            if tag not in recorder.wanted or tag in recorder.jaxprs:
                return entry

            def capturing(*args):
                if tag not in recorder.jaxprs:
                    # make_jaxpr of the EXACT jitted entry the builder
                    # cached — any future change to _shard_map's specs or
                    # wrapping is measured automatically (count_prims
                    # recurses through the outer pjit eqn)
                    recorder.jaxprs[tag] = jax.make_jaxpr(entry)(*args)
                return entry(*args)

            return capturing

        par_ops._shard_map = instrumented
        return self

    def __exit__(self, *exc):
        self._par_ops._shard_map = self._orig
        return False


# ---------------------------------------------------------------------------
# entry-point tracers (one golden file each)
# ---------------------------------------------------------------------------


def _pack_modes() -> Dict[str, str]:
    return {"packed": "1", "perbuf": "0"}


def _trace_shuffle_bucketed(ctx) -> Dict[str, Dict]:
    from ..parallel import ops as par_ops

    out: Dict[str, Dict] = {}
    t = _canonical_table(ctx)
    for label, mode in _pack_modes().items():
        with config.knob_env(CYLON_TPU_SHUFFLE="bucketed",
                             CYLON_TPU_SHUFFLE_PACK=mode):
            with _PlanRecorder(["shuffle"]) as rec:
                par_ops.shuffle(t, (0,))
            out[label] = collect_counts(rec.jaxprs["shuffle"])
    # ISSUE-10 pin: the COMPRESSED exchange stays 1 packed all_to_all +
    # 1 count-matrix all_gather + at most 1 dictionary all_gather (the
    # canonical frame's low-cardinality `tag` column dict-encodes, so
    # the golden records exactly 2 all_gathers) — a regression back to
    # per-buffer or per-dictionary-column collectives fails tier-1
    with config.knob_env(CYLON_TPU_SHUFFLE="bucketed",
                         CYLON_TPU_SHUFFLE_PACK="1",
                         CYLON_TPU_SHUFFLE_COMPRESS="1"):
        with _PlanRecorder(["shuffle"]) as rec:
            par_ops.shuffle(t, (0,))
        out["compressed"] = collect_counts(rec.jaxprs["shuffle"])
    return out


def _trace_task_shuffle(ctx) -> Dict[str, Dict]:
    import numpy as np

    from ..parallel.task import LogicalTaskPlan, task_shuffle
    from ..table import Table

    out: Dict[str, Dict] = {}
    n = GRID["world"] * GRID["shard_cap"] // 2
    arrs = _mixed_frame(n)
    plan = LogicalTaskPlan({3: 0, 5: 2}, GRID["world"])
    for label, mode in _pack_modes().items():
        with config.knob_env(CYLON_TPU_SHUFFLE_PACK=mode):
            ta = Table.from_numpy(list(arrs), list(arrs.values()), ctx=ctx)
            tb = Table.from_numpy(
                list(arrs), [np.concatenate([v[1:], v[:1]])
                             for v in arrs.values()], ctx=ctx)
            with _PlanRecorder(["task_shuffle"]) as rec:
                task_shuffle([ta, tb], [3, 5], plan)
            out[label] = collect_counts(rec.jaxprs["task_shuffle"])
    return out


def _trace_hash_partition(ctx) -> Dict[str, Dict]:
    from ..parallel import ops as par_ops

    out: Dict[str, Dict] = {}
    t = _canonical_table(ctx)
    for label, mode in _pack_modes().items():
        with config.knob_env(CYLON_TPU_SHUFFLE_PACK=mode):
            with _PlanRecorder(["hash_partition"]) as rec:
                par_ops.hash_partition(t, (0,), 3)
            out[label] = collect_counts(rec.jaxprs["hash_partition"])
    return out


def _trace_shuffle_ragged(ctx) -> Dict[str, Dict]:
    """Trace-only (XLA:CPU cannot run RaggedAllToAll)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from .. import column as colmod
    from ..context import PARTITION_AXIS
    from ..parallel import shuffle as shuffle_mod
    from ..utils import shard_map

    world, cap = GRID["world"], GRID["shard_cap"]
    n = world * cap
    arrs = _mixed_frame(n)
    cols = tuple(colmod.from_numpy(a, capacity=n) for a in arrs.values())
    rng = np.random.default_rng(11)
    targets = jnp.asarray(rng.integers(0, world, n).astype(np.int32))

    def fn(cc, tgt):
        out_cols, total = shuffle_mod.shuffle_shard_ragged(cc, tgt, world, n)
        return out_cols, jnp.reshape(total, (1,))

    out: Dict[str, Dict] = {}
    for label, mode in _pack_modes().items():
        with config.knob_env(CYLON_TPU_SHUFFLE_PACK=mode):
            f = jax.jit(shard_map(fn, mesh=ctx.mesh,
                                  in_specs=P(PARTITION_AXIS),
                                  out_specs=P(PARTITION_AXIS),
                                  check_vma=False))
            out[label] = collect_counts(jax.make_jaxpr(f)(cols, targets))
    # compressed ragged body (trace-only like the rest of this entry):
    # spec from the host-side estimate — the same layout the device
    # stats pass would derive on this single-controller grid
    from ..parallel import plane as plane_mod

    with config.knob_env(CYLON_TPU_SHUFFLE_PACK="1",
                         CYLON_TPU_SHUFFLE_COMPRESS="1"):
        spec = plane_mod.estimate_spec(cols, world=world,
                                       shard_cap=n // world)

        def cfn(cc, tgt):
            out_cols, total = shuffle_mod.shuffle_shard_ragged(
                cc, tgt, world, n, spec=spec)
            return out_cols, jnp.reshape(total, (1,))

        f = jax.jit(shard_map(cfn, mesh=ctx.mesh,
                              in_specs=P(PARTITION_AXIS),
                              out_specs=P(PARTITION_AXIS),
                              check_vma=False))
        out["compressed"] = collect_counts(jax.make_jaxpr(f)(cols, targets))
    return out


def _trace_chunked_pass(ctx) -> Dict[str, Dict]:
    """The chunked out-of-core engine's per-pass device program (the
    ``@jax.jit`` closure ``chunked_groupby`` builds per level).  Budget:
    ZERO collectives — the pass program is strictly single-device; an
    accidental pjit sharding or collective here would wedge the
    out-of-core stream on a mesh."""
    import jax
    import jax.numpy as jnp

    from .. import column as colmod
    from ..ops import groupby as groupby_mod
    from ..ops.groupby import AggOp

    n = GRID["shard_cap"]
    arrs = _mixed_frame(n)
    cols = tuple(colmod.from_numpy(a, capacity=n) for a in arrs.values())
    aggs = ((1, AggOp.SUM), (3, AggOp.MEAN))

    def prog(cc, cnt):
        return groupby_mod.hash_groupby(cc, cnt, (0,), aggs, 0)

    jaxpr = jax.make_jaxpr(prog)(cols, jnp.int32(n))
    return {"pass": collect_counts(jaxpr)}


class _LaunchMeter:
    """Counts ENFORCED collective launches across EVERY program
    *invocation* of a run (not just unique programs): the per-PLAN
    budget must see that an eager self-join runs the same cached
    shuffle program twice.  Wraps ``parallel.ops._shard_map`` — the
    only builder whose programs carry collectives; shard-wise local
    programs are collective-free by construction (the chunked_pass
    golden pins that)."""

    def __init__(self):
        self.totals: Dict[str, int] = {p: 0 for p in ENFORCED_PRIMS}
        self._per_entry: Dict[int, Dict[str, int]] = {}

    def __enter__(self):
        import jax

        from ..parallel import ops as par_ops

        self._par_ops = par_ops
        self._orig = par_ops._shard_map
        meter = self

        def instrumented(ctx, fn, key, shapes_key, out_specs=None):
            entry = meter._orig(ctx, fn, key, shapes_key, out_specs)

            def counting(*args):
                counts = meter._per_entry.get(id(entry))
                if counts is None:
                    jaxpr = jax.make_jaxpr(entry)(*args)
                    counts = {p: count_prims(jaxpr.jaxpr, (p,))
                              for p in ENFORCED_PRIMS}
                    meter._per_entry[id(entry)] = counts
                for p, n in counts.items():
                    meter.totals[p] += n
                return entry(*args)

            return counting

        par_ops._shard_map = instrumented
        return self

    def __exit__(self, *exc):
        self._par_ops._shard_map = self._orig
        return False


def _plan_join_groupby_query(ctx):
    """The canonical join→groupby-on-same-key plan: a SELF-join (both
    sides scan the same table) grouped on the join key — the shape
    ROADMAP item 1 names, where the planner's scan sharing + shuffle
    elision collapse 3 eager exchanges (left, right, partials) into
    exactly ONE packed exchange."""
    t = _canonical_table(ctx)
    left = t.plan().project(["k32", "f64"])
    right = t.plan().project(["k32"])
    return (left.join(right, on="k32", how="inner")
            .groupby(["l_k32"], {"f64": ["sum"]}))


def _trace_plan_join_groupby(ctx) -> Dict[str, Dict]:
    """Per-PLAN collective budget: total enforced launches across every
    program invocation of the whole plan run, planner on vs off.  The
    committed golden pins planner=1 all_to_all vs eager=3 — a future
    optimizer edit that silently stops eliding (or an executor edit
    that re-shuffles) regresses this by integer amounts."""
    out: Dict[str, Dict] = {}
    for label, mode in (("planner", "1"), ("eager", "0")):
        with config.knob_env(CYLON_TPU_PLAN=mode,
                             CYLON_TPU_SHUFFLE="bucketed",
                             CYLON_TPU_SHUFFLE_PACK="1"):
            q = _plan_join_groupby_query(ctx)
            with _LaunchMeter() as meter:
                q.execute()
            out[label] = {"collectives": dict(meter.totals),
                          "informational": {}}
    return out


def _plan_broadcast_query(ctx):
    """A fact⋈dim join whose dimension side is tiny: the shape the
    adaptive planner's broadcast-hash rule exists for.  Metadata alone
    (scan column nbytes) is enough to pick the dim side, so no
    statistics catalog is needed."""
    import numpy as np

    from ..table import Table

    world, cap = GRID["world"], GRID["shard_cap"]
    n = world * cap * 4
    rng = np.random.default_rng(17)
    fact = Table.from_numpy(
        ["k", "v"],
        [rng.integers(0, 64, size=n).astype(np.int32),
         rng.standard_normal(n)],
        ctx=ctx, capacity=n)
    dim = Table.from_numpy(
        ["k", "w"],
        [np.arange(64, dtype=np.int32),
         (np.arange(64) % 7).astype(np.int64)],
        ctx=ctx, capacity=64)
    return fact.plan().join(dim.plan(), on="k", how="inner")


def _trace_plan_salted_query(ctx):
    """Zipf-skewed fact⋈dim then NUNIQUE grouped on the (collision-
    prefixed) join key — the one shape the skew-salt rule accepts."""
    import numpy as np

    from ..table import Table

    world, cap = GRID["world"], GRID["shard_cap"]
    n = world * cap * 4
    rng = np.random.default_rng(23)
    k = (np.minimum(rng.zipf(1.3, size=n), 50) - 1).astype(np.int32)
    fact = Table.from_numpy(
        ["k", "u"],
        [k, rng.integers(0, 97, size=n).astype(np.int64)],
        ctx=ctx, capacity=n)
    dim = Table.from_numpy(
        ["k", "w"],
        [np.arange(64, dtype=np.int32),
         np.arange(64, dtype=np.int64)],
        ctx=ctx, capacity=64)
    return (fact.plan().join(dim.plan(), on="k", how="inner")
            .groupby(["l_k"], {"u": ["nunique"]}))


def _trace_plan_broadcast_join(ctx) -> Dict[str, Dict]:
    """Adaptive broadcast-hash join budget: the broadcast arm must move
    the tiny dimension with exactly ONE all_gather and ZERO all_to_all —
    the shuffle arm (adaptive off, same plan) pays two full exchanges.
    Any future edit that un-packs the broadcast plane or sneaks a data
    shuffle back under the broadcast join regresses this golden."""
    out: Dict[str, Dict] = {}
    for label, adaptive in (("broadcast", "1"), ("shuffle", "0")):
        with config.knob_env(CYLON_TPU_PLAN="1",
                             CYLON_TPU_PLAN_ADAPTIVE=adaptive,
                             CYLON_TPU_SHUFFLE="bucketed",
                             CYLON_TPU_SHUFFLE_PACK="1"):
            q = _plan_broadcast_query(ctx)
            with _LaunchMeter() as meter:
                q.execute()
            out[label] = {"collectives": dict(meter.totals),
                          "informational": {}}
    return out


def _trace_plan_salted_groupby(ctx) -> Dict[str, Dict]:
    """Skew-salted NUNIQUE budget.  The statistics catalog is seeded
    OUTSIDE the meter by one profiled adaptive-off run into a throwaway
    stats dir (the salt rule only fires on *observed* catalog skew);
    the salted arm then pays exactly one extra tiny exchange over the
    plain arm — the pre-combine spread across salt buckets."""
    import tempfile

    out: Dict[str, Dict] = {}
    with tempfile.TemporaryDirectory() as stats_dir:
        with config.knob_env(CYLON_TPU_PLAN="1",
                             CYLON_TPU_PLAN_ADAPTIVE="0",
                             CYLON_TPU_SHUFFLE="bucketed",
                             CYLON_TPU_SHUFFLE_PACK="1",
                             CYLON_TPU_PROFILE="1",
                             CYLON_TPU_STATS_DIR=stats_dir):
            _trace_plan_salted_query(ctx).execute()
        for label, adaptive in (("salted", "1"), ("plain", "0")):
            # broadcast threshold 0 keeps the join shuffled in both arms
            # so the delta below is the salt pipeline alone
            with config.knob_env(CYLON_TPU_PLAN="1",
                                 CYLON_TPU_PLAN_ADAPTIVE=adaptive,
                                 CYLON_TPU_PLAN_BROADCAST_BYTES="0",
                                 CYLON_TPU_PLAN_SKEW_SALT="1.2",
                                 CYLON_TPU_SHUFFLE="bucketed",
                                 CYLON_TPU_SHUFFLE_PACK="1",
                                 CYLON_TPU_STATS_DIR=stats_dir):
                q = _trace_plan_salted_query(ctx)
                with _LaunchMeter() as meter:
                    q.execute()
                out[label] = {"collectives": dict(meter.totals),
                              "informational": {}}
    return out


ENTRIES = {
    "shuffle_bucketed": _trace_shuffle_bucketed,
    "task_shuffle": _trace_task_shuffle,
    "hash_partition": _trace_hash_partition,
    "shuffle_ragged": _trace_shuffle_ragged,
    "chunked_pass": _trace_chunked_pass,
    "plan_join_groupby": _trace_plan_join_groupby,
    "plan_broadcast_join": _trace_plan_broadcast_join,
    "plan_salted_groupby": _trace_plan_salted_groupby,
}


def trace_budgets(entries: Optional[Sequence[str]] = None) -> Dict[str, Dict]:
    """Trace every entry point at the canonical grid and return
    {entry: {realization: {"collectives": ..., "informational": ...}}}."""
    ctx = _budget_ctx()
    out: Dict[str, Dict] = {}
    for name in entries or ENTRIES:
        out[name] = ENTRIES[name](ctx)
    return out


# ---------------------------------------------------------------------------
# golden files
# ---------------------------------------------------------------------------


def golden_path(entry: str, budget_dir: Optional[str] = None) -> str:
    return _osp.join(budget_dir or BUDGET_DIR, f"{entry}.json")


def load_golden(entry: str, budget_dir: Optional[str] = None) -> Optional[Dict]:
    path = golden_path(entry, budget_dir)
    if not _osp.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def write_budgets(budget_dir: Optional[str] = None,
                  traced: Optional[Dict[str, Dict]] = None) -> List[str]:
    """(Re)generate the golden files from a live trace; returns the paths."""
    import os as _os

    budget_dir = budget_dir or BUDGET_DIR
    _os.makedirs(budget_dir, exist_ok=True)
    traced = traced if traced is not None else trace_budgets()
    paths = []
    for entry, counts in traced.items():
        doc = {"entry": entry, "grid": GRID, "realizations": counts}
        path = golden_path(entry, budget_dir)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        paths.append(path)
    return paths


def check_budgets(budget_dir: Optional[str] = None,
                  traced: Optional[Dict[str, Dict]] = None) -> List[Finding]:
    """Trace live, compare enforced collective counts against the goldens,
    and return CY201/CY202 findings (empty = within budget)."""
    import glob as _glob
    import os.path as _p

    budget_dir = budget_dir or BUDGET_DIR
    traced = traced if traced is not None else trace_budgets()
    findings: List[Finding] = []
    # reverse pass: a committed golden whose entry point no longer traces
    # is an evaporated pin, not a pass — flag it instead of skipping it
    for path in sorted(_glob.glob(_p.join(budget_dir, "*.json"))):
        entry = _p.splitext(_p.basename(path))[0]
        if entry not in traced:
            findings.append(Finding(
                "CY201", path, 1,
                f"committed golden `{entry}` has no live traced entry — "
                f"its collective budget is no longer enforced",
                "the tracer was removed/renamed or its primitive vanished "
                "from this jax; re-point it or delete the golden "
                "deliberately"))
    for entry, counts in traced.items():
        path = golden_path(entry, budget_dir)
        golden = load_golden(entry, budget_dir)
        if golden is None:
            findings.append(Finding(
                "CY201", path, 1,
                f"no golden budget for entry `{entry}`",
                "run `python -m cylon_tpu.analysis --write-budgets` and "
                "commit the generated file"))
            continue
        for realization, got in counts.items():
            want = golden.get("realizations", {}).get(realization)
            if want is None:
                findings.append(Finding(
                    "CY201", path, 1,
                    f"golden for `{entry}` lacks realization "
                    f"`{realization}`",
                    "regenerate with --write-budgets"))
                continue
            for prim, n_want in want.get("collectives", {}).items():
                n_got = got["collectives"].get(prim, 0)
                if n_got != n_want:
                    findings.append(Finding(
                        "CY202", path, 1,
                        f"`{entry}/{realization}` launches {n_got} x "
                        f"`{prim}` but the committed budget is {n_want}",
                        "an intentional change must update the golden "
                        "(--write-budgets) with the regression justified "
                        "in the commit; an unintentional one just "
                        "reintroduced per-buffer collectives"))
    return findings
