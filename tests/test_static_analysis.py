"""cylint (cylon_tpu.analysis): seeded known-bad fixtures — one per rule,
each asserted to fire with the right rule ID and line — plus the
zero-findings-on-package gate and the collective-budget round trip.

The budget tests double as the tier-1 acceptance meter for PR 2's packed
exchange: the committed golden pins the packed shuffle at exactly ONE
data collective (+1 count-matrix all_gather) per exchange, and the gate
fails when a per-buffer collective is reintroduced.
"""
import json
import os
import textwrap

import pytest

from cylon_tpu import config
from cylon_tpu.analysis import astlint, budgets

PKG_DIR = os.path.dirname(os.path.abspath(astlint.__file__))
PACKAGE = os.path.dirname(PKG_DIR)


def _scan(tmp_path, src, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return astlint.scan_paths([str(p)])


def _rules_at(findings):
    return sorted((f.rule, f.line) for f in findings)


# ---------------------------------------------------------------------------
# seeded known-bad fixtures, one per rule
# ---------------------------------------------------------------------------


def test_cy101_host_sync_hazards(tmp_path):
    found = _scan(tmp_path, """\
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def body(x):
            y = jnp.sum(x)
            if y:
                y = y + 1
            z = float(y)
            w = np.asarray(y)
            v = y.item()
            return y
        """)
    assert _rules_at(found) == [("CY101", 8), ("CY101", 10),
                                ("CY101", 11), ("CY101", 12)]
    assert "tracer truthiness" in found[0].msg
    assert "`float()` on a tracer" in found[1].msg
    assert "np.asarray" in found[2].msg
    assert ".item()" in found[3].msg


def test_cy101_static_predicates_are_legal(tmp_path):
    # dtype/shape/is-None branches are trace-time constants, not hazards
    assert _scan(tmp_path, """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def body(x, other):
            y = jnp.cumsum(x)
            if jnp.issubdtype(y.dtype, jnp.floating):
                y = y + 1
            if y.shape[0] > 4:
                y = y * 2
            if other is None:
                return y
            return y + other
        """) == []


def test_cy101_untraced_function_not_scanned(tmp_path):
    # same hazards outside any jit/shard_map body: host code, legal
    assert _scan(tmp_path, """\
        import jax.numpy as jnp

        def host(x):
            y = jnp.sum(x)
            return float(y)
        """) == []


def test_cy102_stray_env_reads(tmp_path):
    found = _scan(tmp_path, """\
        import os

        def f():
            return os.environ.get("CYLON_TPU_WHATEVER")

        def g():
            return os.getenv("CYLON_TPU_OTHER")

        def h():
            return os.environ["CYLON_TPU_THIRD"]
        """)
    assert _rules_at(found) == [("CY102", 4), ("CY102", 7), ("CY102", 10)]
    assert "knob registry" in found[0].msg


def test_cy102_allows_registry_files():
    # the two sanctioned readers carry direct os.environ reads by design
    cfg = os.path.join(PACKAGE, "config.py")
    cache = os.path.join(PACKAGE, "utils", "compile_cache.py")
    found = astlint.scan_paths([cfg, cache])
    assert [f for f in found if f.rule == "CY102"] == []


def test_cy103_uncached_trace_knob(tmp_path):
    found = _scan(tmp_path, """\
        import jax
        from cylon_tpu.parallel import plane as plane_mod

        _cache = {}

        def my_builder(ctx, fn, key, shapes_key):
            entry = _cache.get(key)
            if entry is None:
                entry = jax.jit(fn)
                _cache[key] = entry
            return entry

        def plan(ctx, t):
            def body(tt):
                if plane_mod.pack_enabled():
                    return tt + 1
                return tt
            return my_builder(ctx, body, ("shuffle", 1), ())

        def plan_keyed(ctx, t):
            def body2(tt):
                if plane_mod.pack_enabled():
                    return tt + 1
                return tt
            return my_builder(ctx, body2,
                              ("shuffle", plane_mod.pack_enabled()), ())
        """)
    assert _rules_at(found) == [("CY103", 18)]
    assert "CYLON_TPU_SHUFFLE_PACK" in found[0].msg


def test_cy103_keyword_only_key_param(tmp_path):
    # the table.py::_shard_wise shape: cache key arrives as a keyword-only
    # param and call sites pass key= — the rule must still see it
    found = _scan(tmp_path, """\
        import jax
        from cylon_tpu.parallel import plane as plane_mod

        _cache = {}

        def shard_wise(ctx, fn, *tables, key):
            entry = _cache.get(key)
            if entry is None:
                entry = jax.jit(fn)
                _cache[key] = entry
            return entry(*tables)

        def select(ctx, t):
            def body(tt):
                if plane_mod.pack_enabled():
                    return tt
                return tt
            return shard_wise(ctx, body, t, key=("select", 1))
        """)
    assert _rules_at(found) == [("CY103", 18)]
    assert "CYLON_TPU_SHUFFLE_PACK" in found[0].msg


def test_cy103_token_complete_builder_is_exempt(tmp_path):
    # a builder that appends config.trace_cache_token() covers every knob
    assert _scan(tmp_path, """\
        import jax
        from cylon_tpu import config
        from cylon_tpu.parallel import plane as plane_mod

        _cache = {}

        def my_builder(ctx, fn, key, shapes_key):
            cache_key = (key, shapes_key, config.trace_cache_token())
            entry = _cache.get(cache_key)
            if entry is None:
                entry = jax.jit(fn)
                _cache[cache_key] = entry
            return entry

        def plan(ctx, t):
            def body(tt):
                if plane_mod.pack_enabled():
                    return tt + 1
                return tt
            return my_builder(ctx, body, ("shuffle", 1), ())
        """) == []


def test_cy104_retried_collective(tmp_path):
    found = _scan(tmp_path, """\
        import jax
        from cylon_tpu import resilience

        def exchange():
            return jax.lax.psum(1, "x")

        def bad(policy):
            return resilience.retry_call(exchange, policy=policy, site="s")

        def bad_lambda(x, policy):
            return resilience.retry_call(
                lambda: jax.lax.all_to_all(x, "x", 0, 0), policy=policy)

        def sanctioned(ctx):
            return resilience.retry_call(
                exchange, policy=ctx.collective_retry_policy(), site="s")
        """)
    assert _rules_at(found) == [("CY104", 8), ("CY104", 11)]
    assert "psum" in found[0].msg
    assert "all_to_all" in found[1].msg


def test_cy105_swallowed_exceptions(tmp_path):
    found = _scan(tmp_path, """\
        def f():
            try:
                return 1
            except:
                return 2

        def g():
            try:
                return 1
            except Exception:
                return 2

        def ok_used():
            try:
                return 1
            except Exception as e:
                return repr(e)

        def ok_reraise():
            try:
                return 1
            except Exception:
                raise
        """)
    assert _rules_at(found) == [("CY105", 4), ("CY105", 10)]
    assert "bare" in found[0].msg


def _scan_elastic(tmp_path, src):
    """CY106 fixtures must live at cylon_tpu/elastic.py for the module
    name to resolve to the elastic recovery namespace."""
    d = tmp_path / "cylon_tpu"
    d.mkdir(exist_ok=True)
    p = d / "elastic.py"
    p.write_text(textwrap.dedent(src))
    return astlint.scan_paths([str(p)])


def test_cy106_unguarded_collective_on_recovery_path(tmp_path):
    found = _scan_elastic(tmp_path, """\
        import jax

        def _reform_mesh(x):
            return jax.lax.psum(x, "p")

        def elastic_resume(agent, x):
            return _reform_mesh(x)
        """)
    assert _rules_at(found) == [("CY106", 6)]
    assert "psum" in found[0].msg and "epoch guard" in found[0].msg


def test_cy106_guarded_recovery_path_is_clean(tmp_path):
    found = _scan_elastic(tmp_path, """\
        import jax

        def _reform_mesh(x):
            return jax.lax.psum(x, "p")

        def elastic_resume(agent, epoch, x):
            agent.ensure_epoch(epoch)
            return _reform_mesh(x)

        def elastic_no_collectives(agent):
            return agent.view()
        """)
    assert found == []


def test_cy106_covers_reconnect_paths(tmp_path):
    """PR 11: reconnect/ride-through paths are recovery roots too — a
    collective issued from a reconnected agent's path against a
    possibly-restarted coordinator is the same stale-world hazard as one
    issued from a resume path."""
    found = _scan_elastic(tmp_path, """\
        import jax

        def _reconnect_loop(agent, x):
            return jax.lax.psum(x, "p")

        def _ride_out_window(agent, epoch, x):
            agent.ensure_epoch(epoch)
            return jax.lax.psum(x, "p")
        """)
    assert _rules_at(found) == [("CY106", 3)]
    assert "psum" in found[0].msg  # the guarded ride_out path is clean


def test_cy106_only_fires_in_the_elastic_module(tmp_path):
    # the same shape outside cylon_tpu.elastic is not a recovery path
    found = _scan(tmp_path, """\
        import jax

        def elastic_resume(x):
            return jax.lax.psum(x, "p")
        """)
    assert "CY106" not in {f.rule for f in found}


def _scan_serve(tmp_path, src):
    """CY107 fixtures must live under cylon_tpu/serve/ for the module
    name to resolve into the serving namespace."""
    d = tmp_path / "cylon_tpu" / "serve"
    d.mkdir(parents=True, exist_ok=True)
    p = d / "service.py"
    p.write_text(textwrap.dedent(src))
    return astlint.scan_paths([str(p)])


def test_cy107_blocking_device_call_on_control_path(tmp_path):
    found = _scan_serve(tmp_path, """\
        import jax

        def _fetch(x):
            return jax.block_until_ready(x)

        class Service:
            def submit(self, x):
                return self._admit_check(x)

            def _admit_check(self, x):
                return _fetch(x)
        """)
    # both the root and the _admit* helper reach the blocking call
    # (self.X calls resolve against same-module functions)
    assert _rules_at(found) == [("CY107", 7), ("CY107", 10)]
    assert "block_until_ready" in found[0].msg
    assert "shedding" in found[0].msg


def test_cy107_executor_device_work_is_clean(tmp_path):
    # device work in the executor (_run_ticket) is the design; only the
    # admission/dispatch control path must stay device-free
    found = _scan_serve(tmp_path, """\
        import jax

        class Service:
            def submit(self, x):
                self._queue.append(x)

            def _dispatch_next(self):
                return self._queue.popleft()

            def _run_ticket(self, x):
                return jax.device_get(x)
        """)
    assert found == []


def test_cy107_only_fires_under_the_serve_package(tmp_path):
    found = _scan(tmp_path, """\
        import jax

        def submit(x):
            return jax.block_until_ready(x)
        """)
    assert "CY107" not in {f.rule for f in found}


def _scan_router(tmp_path, src, name="service.py", extra=()):
    """CY110 fixtures must live under cylon_tpu/router/ for the module
    name to resolve into the router namespace; ``extra`` adds sibling
    fixture files to the same scan (cross-module reachability)."""
    d = tmp_path / "cylon_tpu" / "router"
    d.mkdir(parents=True, exist_ok=True)
    p = d / name
    p.write_text(textwrap.dedent(src))
    paths = [str(p)]
    for rel, esrc in extra:
        ep = tmp_path / "cylon_tpu" / rel
        ep.parent.mkdir(parents=True, exist_ok=True)
        ep.write_text(textwrap.dedent(esrc))
        paths.append(str(ep))
    return astlint.scan_paths(paths)


def test_cy110_blocking_device_call_on_route_path(tmp_path):
    found = _scan_router(tmp_path, """\
        import jax

        def _fetch(x):
            return jax.block_until_ready(x)

        class Router:
            def route(self, req):
                return self._place_candidates(req)

            def _place_candidates(self, req):
                return _fetch(req)
        """)
    # both the route root and the _place* helper reach the blocking
    # call (self.X calls resolve against same-module functions)
    assert _rules_at(found) == [("CY110", 7), ("CY110", 10)]
    assert "block_until_ready" in found[0].msg
    assert "placement" in found[0].msg


def test_cy110_replica_executor_device_work_is_clean(tmp_path):
    # device work behind the proxy verbs (a non-control-path name) is
    # the design; only route/placement/reroute/handler roots must stay
    # device-free
    found = _scan_router(tmp_path, """\
        import jax

        class Router:
            def route(self, req):
                return self._place(req)

            def _place(self, req):
                return sorted(req)

            def run_ticket_on_replica(self, x):
                return jax.device_get(x)
        """)
    assert found == []


def test_cy110_only_fires_under_the_router_package(tmp_path):
    found = _scan(tmp_path, """\
        import jax

        def route(req):
            return jax.block_until_ready(req)
        """)
    assert "CY110" not in {f.rule for f in found}


def test_cy110_arrow_ipc_decode_is_a_host_only_barrier(tmp_path):
    """pyarrow's ``Array.to_numpy`` (the wire codec's IPC decode in
    io/arrow_io.py) shares its final identifier with the device fetch:
    the declared host-only module barrier must keep the handler paths
    riding it clean, while a DIRECT device call still fires."""
    arrow = ("io/arrow_io.py", """\
        def frame_from_ipc_bytes(payload):
            return {f.name: arr.to_numpy() for f, arr in payload}
        """)
    found = _scan_router(tmp_path, """\
        from cylon_tpu.io.arrow_io import frame_from_ipc_bytes

        def _handle_submit(req):
            return frame_from_ipc_bytes(req["payload"])
        """, extra=[arrow])
    assert "CY110" not in {f.rule for f in found}
    found = _scan_router(tmp_path, """\
        import jax
        from cylon_tpu.io.arrow_io import frame_from_ipc_bytes

        def _handle_submit(req):
            return jax.device_put(frame_from_ipc_bytes(req["payload"]))
        """, extra=[arrow])
    # (the unverified decode also draws CY117 — this test is about the
    # host-only barrier, so assert on the CY110 set alone)
    cy110 = [f for f in found if f.rule == "CY110"]
    assert [f.rule for f in cy110] == ["CY110"]
    assert "device_put" in cy110[0].msg


def test_cy111_rpc_under_placement_lock(tmp_path):
    found = _scan_router(tmp_path, """\
        from cylon_tpu.net import control

        class Router:
            def _settle(self, addr, obj):
                with self._router_lock:
                    self._counts["hedges_won"] = 1
                    control.request(addr, obj)
        """)
    assert _rules_at(found) == [("CY111", 5)]
    assert "request" in found[0].msg
    assert "_router_lock" in found[0].msg


def test_cy111_transitive_rpc_under_membership_lock(tmp_path):
    # the with body only calls a local helper; the helper does the RPC
    # — the CY110-style walk must follow the edge
    found = _scan_router(tmp_path, """\
        from cylon_tpu.net import control

        def _notify(addr):
            return control.request(addr, {"cmd": "x"})

        class Router:
            def _breaker_flip(self, addr):
                with self._lock:
                    _notify(addr)
        """)
    assert _rules_at(found) == [("CY111", 8)]


def test_cy111_blocking_after_lock_release_is_clean(tmp_path):
    # snapshot under the lock, block after release — the prescribed
    # shape (and how the breaker/hedge paths are actually written)
    found = _scan_router(tmp_path, """\
        from cylon_tpu.net import control

        class Router:
            def _settle(self, addr, obj):
                with self._router_lock:
                    snap = dict(self._counts)
                return control.request(addr, obj)
        """)
    assert found == []


def test_cy111_closure_defined_under_lock_runs_later(tmp_path):
    # a nested def's body executes after the with exits — only calls
    # LEXICALLY in the with body hold the lock
    found = _scan_router(tmp_path, """\
        from cylon_tpu.net import control

        class Router:
            def _arm(self, addr):
                with self._router_lock:
                    def fire():
                        return control.request(addr, {})
                    self._pending.append(fire)
        """)
    assert found == []


def test_cy111_fsync_under_lock_in_durable(tmp_path):
    dur = ("durable.py", """\
        import os

        class RunJournal:
            def _commit(self, fh):
                with self._lock:
                    os.fsync(fh.fileno())
        """)
    found = _scan_router(tmp_path, "X = 1\n", extra=[dur])
    assert _rules_at(found) == [("CY111", 5)]
    assert "fsync" in found[0].msg


def test_cy111_only_fires_in_scoped_modules(tmp_path):
    found = _scan(tmp_path, """\
        from cylon_tpu.net import control

        def flip(lock, addr):
            with lock:
                return control.request(addr, {})
        """)
    assert "CY111" not in {f.rule for f in found}


def _scan_plan(tmp_path, src, name="executor.py"):
    """CY108 fixtures must live under cylon_tpu/plan/ for the module
    name to resolve into the planner namespace."""
    d = tmp_path / "cylon_tpu" / "plan"
    d.mkdir(parents=True, exist_ok=True)
    p = d / name
    p.write_text(textwrap.dedent(src))
    return astlint.scan_paths([str(p)])


def test_cy108_knob_read_without_fingerprint_coverage(tmp_path):
    found = _scan_plan(tmp_path, """\
        from cylon_tpu.parallel.plane import pack_enabled

        def optimize(plan):
            return pack_enabled()

        def plan_fingerprint(plan):
            return hash(plan)  # trace knobs NOT covered
        """)
    assert [(f.rule, f.line) for f in found if f.rule == "CY108"] \
        == [("CY108", 3)]
    assert "CYLON_TPU_SHUFFLE_PACK" in found[0].msg
    assert "stale" in found[0].msg


def test_cy108_token_complete_fingerprint_is_clean(tmp_path):
    found = _scan_plan(tmp_path, """\
        from cylon_tpu import config
        from cylon_tpu.parallel.plane import pack_enabled

        def optimize(plan):
            return pack_enabled()

        def plan_fingerprint(plan):
            return hash((plan, config.trace_cache_token()))
        """)
    assert "CY108" not in {f.rule for f in found}


def test_cy108_missing_fingerprint_builder_fires(tmp_path):
    # a plan package with NO fingerprint builder at all: the executor
    # reading a trace knob has nothing covering it
    found = _scan_plan(tmp_path, """\
        from cylon_tpu.precision import narrow

        def _exec_agg(t):
            return narrow()
        """)
    assert any(f.rule == "CY108" for f in found)


def test_cy108_only_fires_under_the_plan_package(tmp_path):
    found = _scan(tmp_path, """\
        from cylon_tpu.parallel.plane import pack_enabled

        def optimize(plan):
            return pack_enabled()
        """)
    assert "CY108" not in {f.rule for f in found}


def test_cy112_stats_read_without_strategy_fold(tmp_path):
    # ISSUE-17's bug class: an optimizer rule steering on catalog
    # statistics while the plan fingerprint ignores the chosen strategy
    # — a catalog update would flip the physical plan under an
    # unchanged journal/serve cache key
    found = _scan_plan(tmp_path, """\
        def lookup_stats(plan):
            return None

        def _rule_broadcast_join(p):
            return lookup_stats(p)

        def plan_fingerprint(plan):
            return hash(plan)  # strategy choice NOT folded
        """, name="optimizer.py")
    assert [(f.rule, f.line) for f in found if f.rule == "CY112"] \
        == [("CY112", 4)]
    assert "lookup_stats" in found[0].msg
    assert "unchanged" in found[0].msg


def test_cy112_strategy_folded_fingerprint_is_clean(tmp_path):
    found = _scan_plan(tmp_path, """\
        def strategy_spec(phys):
            return ()

        def lookup_stats(plan):
            return None

        def _rule_broadcast_join(p):
            return lookup_stats(p)

        def plan_fingerprint(plan, phys):
            return hash((plan, strategy_spec(phys)))
        """, name="optimizer.py")
    assert "CY112" not in {f.rule for f in found}


def test_cy112_missing_fingerprint_builder_fires(tmp_path):
    # a plan package with NO fingerprint builder at all: the rule
    # reading column statistics has nothing folding its choice
    found = _scan_plan(tmp_path, """\
        def _rule_salt_agg(p, stats):
            return stats.column_stats("k")
        """, name="optimizer.py")
    assert any(f.rule == "CY112" for f in found)


def test_cy112_only_fires_under_the_plan_package(tmp_path):
    found = _scan(tmp_path, """\
        def _rule_broadcast_join(p, stats):
            return stats.column_stats("k")
        """)
    assert "CY112" not in {f.rule for f in found}


def _scan_stream(tmp_path, src, name="loader.py"):
    """CY116 fixtures must live under cylon_tpu/stream/ for the module
    name to resolve into the streaming namespace."""
    d = tmp_path / "cylon_tpu" / "stream"
    d.mkdir(parents=True, exist_ok=True)
    p = d / name
    p.write_text(textwrap.dedent(src))
    return astlint.scan_paths([str(p)])


def test_cy116_decode_without_version_gate(tmp_path):
    # ISSUE-19's bug class: a combine-layout change silently misreading
    # old partial-aggregate spills — the checksum proves the bytes, the
    # schema version proves the MEANING, and this reader skips the gate
    found = _scan_stream(tmp_path, """\
        def load_state(journal, part):
            frame, rows = journal.load_pass(0, part)
            return frame, rows
        """)
    assert [(f.rule, f.line) for f in found if f.rule == "CY116"] \
        == [("CY116", 1)]
    assert "load_pass" in found[0].msg
    assert "schema version" in found[0].msg


def test_cy116_gated_decode_is_clean(tmp_path):
    found = _scan_stream(tmp_path, """\
        from cylon_tpu.stream.state import require_state_version

        def load_state(journal, part):
            require_state_version(journal.pass_provenance(0, part))
            frame, rows = journal.load_pass(0, part)
            return frame, rows
        """)
    assert "CY116" not in {f.rule for f in found}


def test_cy116_gate_at_a_distance_still_fires(tmp_path):
    # the refactoring hazard the rule exists to kill: the CALLER
    # validates, then the decode is lifted into a helper and the guard
    # silently stops covering it — lexical pairing is the discipline
    found = _scan_stream(tmp_path, """\
        from cylon_tpu.stream.state import require_state_version

        def refresh(journal, part):
            require_state_version(journal.pass_provenance(0, part))
            return _decode(journal, part)

        def _decode(journal, part):
            from cylon_tpu.io.arrow_io import frame_from_ipc_bytes
            return frame_from_ipc_bytes(journal.read_spill(part))
        """)
    assert [(f.rule, f.line) for f in found if f.rule == "CY116"] \
        == [("CY116", 7)]
    assert "frame_from_ipc_bytes" in found[0].msg


def test_cy116_only_fires_under_the_stream_package(tmp_path):
    # durable.py itself (and every non-stream caller of load_pass) is
    # out of scope: the version field is a STREAM-layer contract
    found = _scan(tmp_path, """\
        def resume(journal):
            return journal.load_pass(0, 0)
        """)
    assert "CY116" not in {f.rule for f in found}


# ---------------------------------------------------------------------------
# CY117: spill bytes read outside a checksum-verifying loader (PR 20)
# ---------------------------------------------------------------------------

def _scan_pkg(tmp_path, src, name="durable_helper.py"):
    """CY117 fixtures must resolve INTO the package namespace (the rule
    only polices cylon_tpu code, not user scripts)."""
    d = tmp_path / "cylon_tpu"
    d.mkdir(parents=True, exist_ok=True)
    p = d / name
    p.write_text(textwrap.dedent(src))
    return astlint.scan_paths([str(p)])


def test_cy117_raw_binary_spill_read_fires(tmp_path):
    # the PR-20 bug class: a new code path reads committed .arrow bytes
    # straight off disk — silent bitrot would be served as truth instead
    # of triggering read-repair or quarantine
    found = _scan_pkg(tmp_path, """\
        import os

        def read_spill(run_dir, level, part):
            path = os.path.join(run_dir, f"pass_L{level}_P{part}.arrow")
            with open(path, "rb") as fh:
                return fh.read()
        """)
    assert [(f.rule, f.line) for f in found if f.rule == "CY117"] \
        == [("CY117", 3)]
    assert "bitrot" in found[0].msg


def test_cy117_sha256_verified_read_is_clean(tmp_path):
    found = _scan_pkg(tmp_path, """\
        import hashlib, os

        def read_spill(run_dir, entry):
            path = os.path.join(run_dir, entry["file"])  # a .arrow spill
            with open(path, "rb") as fh:
                data = fh.read()
            if hashlib.sha256(data).hexdigest() != entry["sha256"]:
                raise IOError("spill corrupt")
            return data
        """)
    assert "CY117" not in {f.rule for f in found}


def test_cy117_unverified_ipc_decode_fires_and_loader_is_clean(tmp_path):
    # frame_from_ipc_bytes on unverified bytes is the same hazard with
    # the open() hidden behind a helper; going through the journal's
    # verifying loader (load_pass) is the sanctioned path
    found = _scan_pkg(tmp_path, """\
        from cylon_tpu.io.arrow_io import frame_from_ipc_bytes

        def decode(blob):
            return frame_from_ipc_bytes(blob)

        def sanctioned(journal, part):
            return journal.load_pass(0, part)
        """)
    assert [(f.rule, f.line) for f in found if f.rule == "CY117"] \
        == [("CY117", 3)]
    assert "frame_from_ipc_bytes" in found[0].msg


def test_cy117_outside_package_and_write_mode_are_out_of_scope(tmp_path):
    # a user script is not package code, and a binary WRITE of a spill
    # (the journal's own commit path hashes what it writes) never fires
    src = """\
        def read_spill(path):
            with open(path + ".arrow", "rb") as fh:
                return fh.read()
        """
    assert "CY117" not in {f.rule for f in _scan(tmp_path, src)}
    found = _scan_pkg(tmp_path, """\
        def write_spill(run_dir, name, data):
            with open(run_dir + "/" + name + ".arrow", "wb") as fh:
                fh.write(data)
        """)
    assert "CY117" not in {f.rule for f in found}


_CY109_BUILDER = """\
    import jax
    from cylon_tpu import config
    from cylon_tpu.parallel import plane

    def my_builder(ctx, fn, key, shapes_key):
        cache = {}
        entry = jax.jit(fn)
        cache[(key, shapes_key, config.trace_cache_token())] = entry
        return entry
"""


def test_cy109_realized_layout_missing_from_key(tmp_path):
    # the ISSUE-10 bug class: an observed (data-derived) compression
    # spec baked into a traced body while the plan cache key omits it —
    # a data change would decode under the stale field layout.  The
    # builder is trace_cache_token-complete, which must NOT exempt it
    # (the token covers knobs, not data).
    found = _scan(tmp_path, _CY109_BUILDER + """\

    def bad(ctx, t, stats):
        spec = plane.build_spec(t.columns, stats, 4, 64)
        def body(tt):
            return plane.pack_plane(tt.columns, spec)
        return my_builder(ctx, body, ("shuffle", 4), ())
    """)
    hits = [(f.rule, f.line) for f in found if f.rule == "CY109"]
    assert hits == [("CY109", 15)], _rules_at(found)
    assert "spec" in found[0].msg and "stale field layout" in found[0].msg


def test_cy109_spec_in_key_is_clean(tmp_path):
    found = _scan(tmp_path, _CY109_BUILDER + """\

    def good(ctx, t, stats):
        spec = plane.estimate_spec(t.columns, 4, 64)
        def body(tt):
            return plane.pack_plane(tt.columns, spec)
        return my_builder(ctx, body, ("shuffle", 4, spec), ())
    """)
    assert "CY109" not in {f.rule for f in found}, _rules_at(found)


def test_cy109_no_realized_values_is_clean(tmp_path):
    # closures that never touch a realized-layout value are out of scope
    found = _scan(tmp_path, _CY109_BUILDER + """\

    def plain(ctx, t):
        def body(tt):
            return plane.pack_plane(tt.columns)
        return my_builder(ctx, body, ("shuffle",), ())
    """)
    assert "CY109" not in {f.rule for f in found}, _rules_at(found)


def test_cy001_suppression_requires_justification(tmp_path):
    # no justification: the suppression itself is the finding (and does
    # not silence the underlying rule)
    found = _scan(tmp_path, """\
        import os

        def f():
            return os.getenv("CYLON_TPU_X")  # cylint: disable=CY102
        """)
    assert sorted(f.rule for f in found) == ["CY001", "CY102"]

    # with justification: the underlying finding is suppressed
    found = _scan(tmp_path, """\
        import os

        def f():
            return os.getenv("CYLON_TPU_X")  # cylint: disable=CY102 -- fixture exercising the suppression syntax
        """, name="ok.py")
    assert found == []


# ---------------------------------------------------------------------------
# the package itself is clean
# ---------------------------------------------------------------------------


def test_zero_findings_on_package():
    found = astlint.scan_paths([PACKAGE])
    assert found == [], "\n".join(f.render() for f in found)


def test_cli_main_smoke(tmp_path, capsys):
    from cylon_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    assert main(["--knobs"]) == 0
    out = capsys.readouterr().out
    assert "CY101" in out and "CYLON_TPU_SHUFFLE_PACK" in out
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nV = os.getenv('CYLON_TPU_Y')\n")
    assert main([str(bad)]) == 1


# ---------------------------------------------------------------------------
# knob registry
# ---------------------------------------------------------------------------


def test_knob_defaults_and_parsing(monkeypatch):
    for k in config.KNOBS.values():
        monkeypatch.delenv(k.name, raising=False)
        assert config.knob(k.name) == k.default, k.name
    monkeypatch.setenv("CYLON_TPU_PREFETCH", "0")
    assert config.knob("CYLON_TPU_PREFETCH") is False
    monkeypatch.setenv("CYLON_TPU_RETRY_MAX", "7")
    assert config.knob("CYLON_TPU_RETRY_MAX") == 7
    monkeypatch.setenv("CYLON_TPU_RETRY_MAX", "junk")
    assert config.knob("CYLON_TPU_RETRY_MAX") == 2  # parse error -> default
    monkeypatch.setenv("CYLON_TPU_SHUFFLE_PACK", "bogus")
    assert config.knob("CYLON_TPU_SHUFFLE_PACK") == "auto"  # enum guard
    with pytest.raises(KeyError):
        config.knob_raw("CYLON_TPU_NOT_A_KNOB")


def test_knob_env_roundtrip(monkeypatch):
    monkeypatch.delenv("CYLON_TPU_SHUFFLE_PACK", raising=False)
    before = config.trace_cache_token()
    with config.knob_env(CYLON_TPU_SHUFFLE_PACK="1"):
        during = config.trace_cache_token()
        assert ("CYLON_TPU_SHUFFLE_PACK", "1") in during
    assert config.trace_cache_token() == before
    with pytest.raises(KeyError):
        with config.knob_env(CYLON_TPU_NOT_A_KNOB="1"):
            pass


def test_registry_covers_every_trace_accessor():
    # every trace-scope knob names at least one accessor, and the
    # accessor's module path exists in the package (guards against the
    # registry drifting from a refactor)
    import importlib

    for k in config.KNOBS.values():
        if k.scope != config.TRACE:
            continue
        assert k.cache_key, f"{k.name}: trace-scope implies cache-key"
        assert k.accessors, f"{k.name}: trace-scope knob without accessors"
        for acc in k.accessors:
            mod_name, fn_name = acc.rsplit(".", 1)
            mod = importlib.import_module(mod_name)
            assert hasattr(mod, fn_name), f"{acc} does not exist"


# ---------------------------------------------------------------------------
# collective budgets (level 2)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced():
    return budgets.trace_budgets()


def test_budget_gate_against_committed_goldens(traced):
    found = budgets.check_budgets(traced=traced)
    assert found == [], "\n".join(f.render() for f in found)


def test_committed_golden_pins_single_collective():
    """The acceptance meter: packed exchange = exactly 1 data collective
    (+1 count all_gather); per-buffer = 13 for the 6-column grid."""
    golden = budgets.load_golden("shuffle_bucketed")
    assert golden is not None, "shuffle_bucketed.json not committed"
    packed = golden["realizations"]["packed"]["collectives"]
    perbuf = golden["realizations"]["perbuf"]["collectives"]
    assert packed["all_to_all"] == 1
    assert packed["all_gather"] == 1
    assert packed["ragged_all_to_all"] == 0
    assert perbuf["all_to_all"] == 13
    task = budgets.load_golden("task_shuffle")["realizations"]
    assert task["packed"]["collectives"]["all_to_all"] == 1
    chunk = budgets.load_golden("chunked_pass")["realizations"]["pass"]
    assert sum(chunk["collectives"].values()) == 0


def test_budget_write_read_roundtrip(tmp_path, traced):
    paths = budgets.write_budgets(str(tmp_path), traced=traced)
    assert paths and all(os.path.exists(p) for p in paths)
    assert budgets.check_budgets(str(tmp_path), traced=traced) == []


def test_budget_regression_detected(tmp_path, traced):
    """Reintroducing a per-buffer collective (1 -> 13) must fail."""
    budgets.write_budgets(str(tmp_path), traced=traced)
    path = budgets.golden_path("shuffle_bucketed", str(tmp_path))
    doc = json.load(open(path))
    doc["realizations"]["packed"]["collectives"]["all_to_all"] = 1
    json.dump(doc, open(path, "w"))
    tampered = {k: v for k, v in traced.items()}
    import copy

    tampered["shuffle_bucketed"] = copy.deepcopy(traced["shuffle_bucketed"])
    tampered["shuffle_bucketed"]["packed"]["collectives"]["all_to_all"] = 13
    found = budgets.check_budgets(str(tmp_path), traced=tampered)
    assert [f.rule for f in found] == ["CY202"]
    assert "13" in found[0].msg and "shuffle_bucketed/packed" in found[0].msg


def test_budget_missing_golden_detected(tmp_path, traced):
    found = budgets.check_budgets(str(tmp_path), traced=traced)
    assert found and all(f.rule == "CY201" for f in found)


def test_count_prims_shared_with_shuffle_pack():
    # the refactor satellite: one meter, two consumers
    from cylon_tpu.analysis.budgets import count_prims

    import tests.test_shuffle_pack as tsp

    assert tsp._count_prims is count_prims


# ---------------------------------------------------------------------------
# Level 3: concurrency (CY113/CY114/CY115 + lock-graph golden + recorder)
# ---------------------------------------------------------------------------


def test_cy113_lock_order_cycle(tmp_path):
    found = _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fwd(self):
                with self._a:
                    with self._b:
                        pass

            def rev(self):
                with self._b:
                    with self._a:
                        pass
        """)
    assert [f.rule for f in found] == ["CY113"]
    assert found[0].line in (10, 14)  # the inner (witness) acquisition


def test_cy113_transitive_inversion_through_calls(tmp_path):
    # the inversion only exists through the call graph: fwd nests a->b
    # lexically, rev holds b and CALLS a helper that takes a
    found = _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def _take_a(self):
                with self._a:
                    pass

            def fwd(self):
                with self._a:
                    with self._b:
                        pass

            def rev(self):
                with self._b:
                    self._take_a()
        """)
    assert [f.rule for f in found] == ["CY113"]


def test_cy113_self_reacquire_non_reentrant(tmp_path):
    found = _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()

            def f(self):
                with self._a:
                    with self._a:
                        pass
        """)
    assert _rules_at(found) == [("CY113", 9)]


def test_cy113_consistent_ordering_is_clean(tmp_path):
    assert _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def f(self):
                with self._a:
                    with self._b:
                        pass

            def g(self):
                with self._a:
                    with self._b:
                        pass
        """) == []


def test_cy114_sleep_under_lock(tmp_path):
    found = _scan(tmp_path, """\
        import threading
        import time

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                with self._lock:
                    time.sleep(0.1)
        """)
    assert _rules_at(found) == [("CY114", 10)]


def test_cy114_transitive_sleep_through_callee(tmp_path):
    # private helper's only call site holds the lock, so the sleep in
    # the helper is reachable while the lock is held
    found = _scan(tmp_path, """\
        import threading
        import time

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def _nap(self):
                time.sleep(0.1)

            def f(self):
                with self._lock:
                    self._nap()
        """)
    # fires at the sleep itself (entry-held) and at the call site (via)
    assert found and all(f.rule == "CY114" for f in found)


def test_cy114_wait_on_own_condition_is_legal(tmp_path):
    # Condition.wait releases its OWN lock while blocking -- only a
    # wait while holding a DIFFERENT lock is a hazard
    found = _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._cv = threading.Condition()
                self._other = threading.Lock()

            def ok(self):
                with self._cv:
                    self._cv.wait(0.1)

            def bad(self):
                with self._other:
                    with self._cv:
                        self._cv.wait(0.1)
        """)
    assert [f.rule for f in found] == ["CY114"]
    assert found[0].line == 15  # the wait under the foreign lock


def test_cy114_sleep_after_release_is_clean(tmp_path):
    assert _scan(tmp_path, """\
        import threading
        import time

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                with self._lock:
                    pass
                time.sleep(0.1)
        """) == []


def test_cy115_unguarded_cross_thread_write(tmp_path):
    found = _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._t = threading.Thread(target=self._loop, daemon=True)

            def _loop(self):
                self.count += 1

            def bump(self):
                self.count += 1
        """)
    assert [f.rule for f in found] == ["CY115"]
    assert found[0].line in (10, 13)
    assert "count" in found[0].msg


def test_cy115_guarded_writes_are_clean(tmp_path):
    assert _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._t = threading.Thread(target=self._loop, daemon=True)

            def _loop(self):
                with self._lock:
                    self.count += 1

            def bump(self):
                with self._lock:
                    self.count += 1
        """) == []


def test_cy115_single_root_is_clean(tmp_path):
    # no spawn in the class: every write happens on the caller's thread
    assert _scan(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def bump(self):
                self.count += 1

            def reset(self):
                self.count = 0
        """) == []


# ---------------------------------------------------------------------------
# lock-graph golden round trip + recorder
# ---------------------------------------------------------------------------


def test_lockgraph_roundtrip_and_injected_inversion(tmp_path):
    from cylon_tpu.analysis import locks

    a = "m.S._a"
    b = "m.S._b"
    observed = {(a, b)}
    static = {(a, b), (a, "m.S._c")}
    path = locks.write_lockgraph(observed, static, str(tmp_path))
    doc = json.load(open(path))
    assert doc["edges"] == [{"src": a, "dst": b}]
    assert doc["static_only"] == [{"src": a, "dst": "m.S._c"}]

    # clean: observed covered by golden and static
    assert locks.check_lockgraph(observed, static, str(tmp_path)) == []

    # injected inversion: the recorder sees b->a, the golden does not
    found = locks.check_lockgraph({(a, b), (b, a)}, static | {(b, a)},
                                  str(tmp_path))
    assert [f.rule for f in found] == ["CY204"]
    assert f"{b} -> {a}" in found[0].msg

    # analyzer coverage loss: observed edge not derivable statically
    found = locks.check_lockgraph({(a, b), (b, a)}, static, str(tmp_path))
    assert [f.rule for f in found] == ["CY204", "CY204"]
    assert "not derivable" in found[1].msg


def test_lockgraph_missing_golden(tmp_path):
    from cylon_tpu.analysis import locks

    found = locks.check_lockgraph({("x", "y")}, set(),
                                  str(tmp_path / "nope"))
    assert [f.rule for f in found] == ["CY203"]


def test_lock_recorder_observes_inversion():
    """Two threads forcing A->B and B->A: the recorder must observe both
    directed edges, and the cycle must be detectable in the edge set."""
    import threading

    from cylon_tpu.analysis import locks

    rec = locks.LockRecorder()
    with locks.record_locks(rec):
        a = threading.Lock()
        b = threading.Lock()

        def fwd():
            with a:
                with b:
                    pass

        def rev():
            with b:
                with a:
                    pass

        t1 = threading.Thread(target=fwd)
        t1.start()
        t1.join()
        t2 = threading.Thread(target=rev)
        t2.start()
        t2.join()

    # raw edges are keyed by creation site (this test file); both
    # orders must have been captured
    edges = set(rec.edges)
    assert len({s for e in edges for s in e}) == 2
    (sa, sb) = sorted({s for e in edges for s in e})
    assert (sa, sb) in edges and (sb, sa) in edges

    succ = {}
    for s, d in edges:
        succ.setdefault(s, set()).add(d)
    cycles = [c for c in locks._sccs({sa, sb}, succ) if len(c) > 1]
    assert cycles, "the A->B / B->A inversion must form a cycle"


def test_lock_recorder_ignores_unknown_sites():
    # observed() maps creation sites through the static inventory; locks
    # created outside the package (tests, stdlib) must be dropped
    import threading

    from cylon_tpu.analysis import locks

    rec = locks.LockRecorder()
    with locks.record_locks(rec):
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
    assert rec.edges  # raw edge captured...
    assert rec.observed() == set()  # ...but maps to nothing


def test_committed_lockgraph_matches_static():
    """The committed golden must be internally consistent with the
    current static graph: every golden edge statically derivable, and
    the merged graph acyclic."""
    from cylon_tpu.analysis import locks

    golden = locks.load_golden()
    assert golden is not None, "lock_order.json must be committed"
    static = locks.static_edges()
    gold = {(e["src"], e["dst"]) for e in golden["edges"]}
    assert gold <= static, sorted(gold - static)

    succ = {}
    nodes = set()
    for s, d in static | gold:
        succ.setdefault(s, set()).add(d)
        nodes.update((s, d))
    assert [c for c in locks._sccs(nodes, succ) if len(c) > 1] == []
