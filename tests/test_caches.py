"""Regression tests for per-context capability/program caches.

Round-2 verdict items: the ragged-collective probe must be keyed by
context (not a module global shared across backends), and the shard-fn
program cache — whose select entries are keyed by predicate object —
must be size-bounded so ad-hoc lambdas cannot leak compiled programs.
"""
import numpy as np
import pytest

from cylon_tpu.context import CylonContext, LRUCache, TPUConfig, ctx_cache
from cylon_tpu.parallel import ops as par_ops
from cylon_tpu.table import Table


def test_lru_cache_bound_and_recency():
    c = LRUCache(maxsize=4)
    for i in range(10):
        c[i] = i * 10
    assert len(c) == 4
    assert set(c) == {6, 7, 8, 9}
    assert c.get(6) == 60        # refresh 6
    c[100] = 1                   # evicts 7 (oldest unrefreshed)
    assert 6 in c and 7 not in c

    # overwriting an existing key must not evict anything
    c[8] = 0
    assert len(c) == 4


def test_ctx_cache_maxsize_honored_at_creation(local_ctx):
    c = ctx_cache(local_ctx, "_test_lru", maxsize=2)
    assert isinstance(c, LRUCache)
    c["a"] = 1
    # second lookup returns the same object regardless of maxsize arg
    assert ctx_cache(local_ctx, "_test_lru") is c


def test_ragged_probe_isolated_per_context(ctx2, monkeypatch):
    """A second context must run its own probe — a CPU-mesh verdict must
    never leak onto a (hypothetical) TPU-mesh context in one process."""
    other = CylonContext.InitDistributed(TPUConfig(world_size=2))
    for ctx in (ctx2, other):
        cache = ctx_cache(ctx, "_ragged_probe")
        cache.pop("ragged", None)

    calls = []

    def fake_probe(ctx):
        calls.append(ctx)
        return len(calls) == 1  # first ctx: True, second: False

    monkeypatch.setattr(par_ops, "_probe_ragged", fake_probe)
    assert par_ops._ragged_enabled(ctx2) is True
    assert par_ops._ragged_enabled(other) is False
    # each context probed exactly once, and re-queries hit the cache
    assert par_ops._ragged_enabled(ctx2) is True
    assert calls == [ctx2, other]
    ctx_cache(ctx2, "_ragged_probe").pop("ragged", None)
    ctx_cache(other, "_ragged_probe").pop("ragged", None)


def test_select_predicate_cache_is_bounded(ctx2):
    """The shard-fn cache entry keyed by a select predicate must live in an
    LRU so distinct lambdas cannot grow the cache without bound."""
    t = Table.from_numpy(
        ["k", "v"],
        [np.arange(64, dtype=np.int32), np.ones(64, dtype=np.float32)],
        ctx=ctx2)
    out = t.select(lambda env: env["k"] < 10)
    assert out.row_count == 10
    cache = ctx_cache(ctx2, "_shard_fn_cache")
    assert isinstance(cache, LRUCache)
    assert cache.maxsize == 256
    assert len(cache) <= cache.maxsize


def test_perm_by_target_clips_out_of_range(ctx2):
    """An out-of-range target must not silently collide destinations into
    slot 0 (it now clips to the padding bucket)."""
    import jax.numpy as jnp

    from cylon_tpu.parallel import shuffle as shuffle_mod

    targets = jnp.asarray([0, 1, 99, -3, 1, 0], jnp.int32)
    perm, = shuffle_mod._perm_by_target(targets, world=2)
    # a valid permutation: every source row appears exactly once
    assert sorted(np.asarray(perm).tolist()) == list(range(6))
