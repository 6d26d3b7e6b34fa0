"""Break down the fused join+groupby pipeline's steady-state cost on TPU.

Times each stage of the join+groupby pipeline separately at ROWS per side
(plus the fused end-to-end program) on the host clock, forcing a tiny host
fetch per rep.

Usage: python tools/profile_pipeline.py [rows_per_side]
"""
import os
import sys
import time

os.environ.setdefault("CYLON_TPU_ACCUM", "narrow")
import jax
import jax.numpy as jnp
import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
from cylon_tpu.utils.compile_cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()
import cylon_tpu  # noqa: F401,E402
from cylon_tpu import column as colmod
from cylon_tpu.obs import export as obs_export
from cylon_tpu.obs import spans as obs_spans
from cylon_tpu.config import JoinType
from cylon_tpu.ops import common, compact, groupby as groupby_mod
from cylon_tpu.ops import join as join_mod, segments
from cylon_tpu.ops.groupby import AggOp
from cylon_tpu.table import _cap_round

ROWS = int(sys.argv[1]) if len(sys.argv) > 1 else (1 << 25)
SEED = 12345
REPS = 3

rng = np.random.default_rng(SEED)
lk = rng.integers(0, ROWS, ROWS).astype(np.int32)
lv = rng.random(ROWS).astype(np.float32)
rk = rng.integers(0, ROWS, ROWS).astype(np.int32)
rv = rng.random(ROWS).astype(np.float32)

cols_l = (colmod.from_numpy(lk), colmod.from_numpy(lv))
cols_r = (colmod.from_numpy(rk), colmod.from_numpy(rv))
count = jnp.asarray(ROWS, jnp.int32)
cap = ROWS


def _touch(out):
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(jax.device_get(leaf[:1]))


def timed(name, fn, *args, traffic_bytes=None):
    """traffic_bytes: MINIMUM HBM traffic for the stage (each operand set
    read once + written once).  The printed GB/s(min) over the chip's
    peak (~819 GB/s on v5e) bounds the stage's efficiency from above —
    the roofline column the round-4 verdict asked for; a stage far below
    peak is re-traversing or serializing."""
    with obs_spans.span("profile.warm", stage=name):
        out = fn(*args)
        _touch(out)
    ts = []
    for _ in range(REPS):
        with obs_spans.span("profile.rep", stage=name):
            t0 = time.perf_counter()
            out = fn(*args)
            _touch(out)
            ts.append(time.perf_counter() - t0)
    sec = min(ts)
    gbs = ""
    if traffic_bytes:
        rate = traffic_bytes / sec / 1e9
        gbs = f" {rate:7.1f} GB/s(min) {100 * rate / 819:5.1f}%v5e-peak"
    print(f"{name:34s} {sec*1e3:10.1f} ms{gbs}", flush=True)
    return out


# -- stage 1: the combined lexsort + run boundaries ------------------------
@jax.jit
def stage_sort(cl, cr, cnt):
    perm, _, new_group, is_run_end, live_sorted = common.combined_sorted_runs(
        cl, cnt, cr, cnt, (0,), (0,))
    return perm, new_group, is_run_end, live_sorted

N2 = 2 * ROWS
sorted_parts = timed("combined sort + run boundaries", stage_sort,
                     cols_l, cols_r, count,
                     traffic_bytes=N2 * 8 * 2 + N2 * 3)

# -- stage 1b: sort-mode A/B on identical operands -------------------------
# CYLON_TPU_SORT is read at TRACE time, so each variant gets its own jit
# function and the env is set around its first (tracing) call.  The perm
# must agree exactly with the cmp path's (ties resolved by embedded index
# in both), so agreement is asserted on device before timing is trusted.
def _sort_variant(label, env):
    for k, v in env.items():
        os.environ[k] = v

    @jax.jit
    def stage(cl, cr, cnt):
        perm, _, new_group, is_run_end, live_sorted = \
            common.combined_sorted_runs(cl, cnt, cr, cnt, (0,), (0,))
        return perm, new_group, is_run_end, live_sorted

    try:
        out = timed(label, stage, cols_l, cols_r, count)
        same = bool(jax.device_get(jnp.array_equal(out[0], sorted_parts[0])))
        print(f"{label:34s} perm agrees with cmp: {same}", flush=True)
        if not same:  # loud: the timings above must not be trusted
            raise SystemExit(f"{label}: PERM MISMATCH vs cmp — radix "
                             f"timings in this profile are INVALID")
    except SystemExit:
        raise
    except Exception as e:  # one variant's compile failure on this
        # backend must not eat the others' measurements
        print(f"{label:34s} FAILED: {type(e).__name__}: {str(e)[:200]}",
              flush=True)
    finally:
        for k in env:
            os.environ.pop(k, None)

if not os.environ.get("CYLON_TPU_PROFILE_SKIP_RADIX"):
    _sort_variant("combined sort RADIX d=1", {"CYLON_TPU_SORT": "radix"})
    _sort_variant("combined sort RADIX d=2",
                  {"CYLON_TPU_SORT": "radix", "CYLON_TPU_RADIX_BITS": "2"})
    _sort_variant("combined sort RADIX d=1 xla-scan",
                  {"CYLON_TPU_SORT": "radix", "CYLON_TPU_RADIX_SCAN": "xla"})

# -- stage 2: run extents (prefix arithmetic) ------------------------------
def _mode_variant(label, setter, mode, stage_fn, args, traffic_bytes,
                  compare_to=None):
    """Shared scaffold for the per-stage mode A/Bs: pin the mode via its
    cache-clearing setter (an env knob alone would let ambient
    CYLON_TPU_* collapse the A/B into a mode vs itself — both arms are
    pinned, baseline included), jit fresh, time, optionally assert exact
    agreement (mismatch is FATAL like the stage-1b sort A/B: mismatched
    timings must not be trusted), restore."""
    setter(mode)

    stage = jax.jit(stage_fn)
    try:
        out = timed(label, stage, *args, traffic_bytes=traffic_bytes)
        if compare_to is not None:
            same = bool(jax.device_get(
                jnp.all(jnp.stack([jnp.array_equal(a, b) for a, b
                                   in zip(out, compare_to)]))))
            print(f"{label:34s} agrees with baseline: {same}", flush=True)
            if not same:
                raise SystemExit(f"{label}: MISMATCH vs baseline — its "
                                 f"timing in this profile is INVALID")
        return out
    except SystemExit:
        raise
    except Exception as e:
        print(f"{label:34s} FAILED: {type(e).__name__}: {str(e)[:200]}",
              flush=True)
        return None
    finally:
        setter(None)


def _extents_stage(perm, new_group, is_run_end, live_sorted):
    is_right = perm >= cap
    return segments.run_extents(is_right & live_sorted, new_group,
                                is_run_end)


# baseline pinned to XLA scans (not the ambient env) so the A/B labels
# are always true
extents = _mode_variant("run extents (XLA scans)", segments.set_scan,
                        "xla", _extents_stage, sorted_parts,
                        N2 * (3 + 4 * 4))
if extents is None:
    raise SystemExit("baseline run-extents stage failed; downstream "
                     "stages cannot be timed")
_mode_variant("run extents (PALLAS scan_1d)", segments.set_scan, "pallas",
              _extents_stage, sorted_parts, N2 * (3 + 4 * 4),
              compare_to=extents)

# -- stage 3: back-map + partition (the real _match_ranges tail) -----------
# Realized per compact.permute_mode() — the inverse-permute back-map and
# the right/left partition are the scatters the sort mode replaces.
@jax.jit
def stage_back(perm, lo_sorted, matches_sorted):
    back = compact.inverse_permute(perm, lo_sorted, matches_sorted)
    is_right = perm >= cap
    part, _ = compact.partition_indices(is_right)
    perm_r = jnp.take(perm, part[:cap]) - cap
    left_key_order = jnp.take(perm, part[cap:])
    return back, perm_r, left_key_order

timed(f"back-map + partition ({compact.permute_mode()})", stage_back,
      sorted_parts[0], extents[0], extents[1],
      traffic_bytes=N2 * 4 * (3 * 2 + 2 * 2 + 3))


def _permute_variant(label, env):
    """Re-time the back-map stage under another permute/invperm
    realization (``env``: the CYLON_TPU_* vars to pin; read at trace
    time, so the stage jits fresh per variant)."""
    for k, v in env.items():
        os.environ[k] = v

    @jax.jit
    def stage(perm, lo_sorted, matches_sorted):
        back = compact.inverse_permute(perm, lo_sorted, matches_sorted)
        is_right = perm >= cap
        part, _ = compact.partition_indices(is_right)
        return back, jnp.take(perm, part[:cap]) - cap

    try:
        timed(label, stage, sorted_parts[0], extents[0], extents[1])
    except Exception as e:
        print(f"{label:34s} FAILED: {type(e).__name__}: {str(e)[:200]}",
              flush=True)
    finally:
        for k in env:
            os.environ.pop(k, None)


other = "scatter" if compact.permute_mode() == "sort" else "sort"
_permute_variant(f"back-map + partition ({other})",
                 {"CYLON_TPU_PERMUTE": other})
# sort-family gather realization of the back-map's inverse_permute
# (CYLON_TPU_INVPERM=gather): one 2-op argsort + linear takes vs the
# multi-operand carry sort
_permute_variant("back-map + partition (sort/gather)",
                 {"CYLON_TPU_PERMUTE": "sort", "CYLON_TPU_INVPERM": "gather"})

# -- full join_gather ------------------------------------------------------
m = int(join_mod.join_row_count(cols_l, count, cols_r, count, (0,), (0,),
                                JoinType.INNER, "sort"))
out_cap = _cap_round(m)
print(f"join count {m}  out_cap {out_cap}", flush=True)


def make_full_join(cap):
    @jax.jit
    def full_join(cl, cr, cnt):
        return join_mod.join_gather(cl, cnt, cr, cnt, (0,), (0,),
                                    JoinType.INNER, cap, "sort",
                                    key_grouped=True, project=(0, 1, 3))
    return full_join

full_join = make_full_join(out_cap)

joined = timed("join_gather total", full_join, cols_l, cols_r, count,
               traffic_bytes=N2 * 8 * 2 + N2 * 4 * 14 + out_cap * 4 * 6)

# -- groupby on joined -----------------------------------------------------
def _gb_stage(jcols, jm):
    return groupby_mod.pipeline_groupby(jcols, jm, (0,),
                                        ((1, AggOp.SUM), (2, AggOp.MEAN)), 0)


# every segsum realization pinned explicitly (ambient CYLON_TPU_SEGSUM
# cannot relabel an arm; no agreement assert — float accumulation order
# legitimately differs across realizations)
for _label, _mode in (("pipeline_groupby (segsum prefix)", "prefix"),
                      ("pipeline_groupby (segsum scatter)", "scatter"),
                      ("pipeline_groupby (segsum PALLAS)", "pallas")):
    _mode_variant(_label, segments.set_segsum, _mode, _gb_stage,
                  (joined[0], joined[1]), out_cap * 4 * 8)

# -- shuffle exchange, local half: packed plane vs per-buffer --------------
# ISSUE-2 tentpole A/B arm.  The collective-launch saving needs a mesh
# (battery step 7d's CPU-mesh scaling A/B); what the chip must answer is
# whether pack + ONE plane gather + unpack beats the per-buffer gathers
# on the same rows — the local half of shuffle_shard under either value
# of CYLON_TPU_SHUFFLE_PACK.
from cylon_tpu.parallel import plane as plane_mod  # noqa: E402

cols4 = cols_l + cols_r
perm_sh = jnp.asarray(rng.permutation(ROWS).astype(np.int32))
live_sh = jnp.asarray(np.arange(ROWS) < int(ROWS * 0.9))
W4 = plane_mod.plane_words(cols4)


@jax.jit
def shuffle_local_packed(cs, idx, m):
    p = plane_mod.pack_plane(cs)
    return plane_mod.unpack_plane(jnp.take(p, idx, axis=0), cs,
                                  valid_mask=m)


@jax.jit
def shuffle_local_perbuf(cs, idx, m):
    return tuple(col.take(idx, valid_mask=m) for col in cs)


# per row: 2x(i32+f32 data) + 4 validity bytes in; gathered copy out
_SHUF_B = (4 + 4) * 2 + 4
timed(f"shuffle local half PACKED ({W4} words)", shuffle_local_packed,
      cols4, perm_sh, live_sh, traffic_bytes=(_SHUF_B + 3 * 4 * W4) * ROWS)
timed("shuffle local half per-buffer (8 bufs)", shuffle_local_perbuf,
      cols4, perm_sh, live_sh, traffic_bytes=2 * _SHUF_B * ROWS)

# -- fused end-to-end ------------------------------------------------------
from hbm_budget import make_fused_pipeline  # noqa: E402  (tools/ sibling)

pipeline = make_fused_pipeline(out_cap, "sort")
timed("FULL fused pipeline", pipeline, cols_l, count, cols_r, count,
      traffic_bytes=N2 * 8 * 2 + N2 * 4 * 14 + out_cap * 4 * 14)
# ISSUE-4: the Perfetto artifact of this exact profile run, when event
# tracing is on — stage labels ride the span attrs
if obs_spans.events_enabled():
    _tp, _mp = obs_export.export_all(prefix="profile")
    print(f"trace artifact: {_tp}", flush=True)
    print(f"metrics artifact: {_mp}", flush=True)
print(f"done @ {ROWS} rows/side", flush=True)
