"""Plan engine: cache identity semantics, no-replan guarantee, trace
ergonomics.

The acceptance criterion for the plan-once/execute-many refactor:
``plan_merge``/``plan_rowsplit`` run at most once per sparsity pattern in
a jitted train/serve loop — asserted here with a cache-hit counter and
with call counters monkeypatched onto the planning phase itself.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import (ExecutionConfig, Heuristic, PlanPolicy,
                        build_plan, execute_plan, pattern_fingerprint,
                        random_csr, spmm)
from repro.kernels import merge_spmm, ops, ref, rowsplit_spmm
from repro.models.sparse import SparseLinear
from repro.runtime import steps as R


def _csr(seed=0, m=32, k=24, npr=(0, 8)):
    return random_csr(jax.random.PRNGKey(seed), m, k, nnz_per_row=npr)


def _with_vals(a, seed):
    vals = jax.random.normal(jax.random.PRNGKey(seed), a.vals.shape)
    return dataclasses.replace(a, vals=vals)


# ------------------------------------------------------------ cache hits ---


def test_cache_hit_same_pattern_different_values():
    cache = engine.PlanCache()
    a = _csr(0)
    p1 = cache.get(a)
    p2 = cache.get(_with_vals(a, 7))        # same pattern, new values
    assert p1 is p2
    s = cache.stats()
    assert (s.hits, s.misses) == (1, 1)


def test_cache_miss_different_pattern():
    cache = engine.PlanCache()
    cache.get(_csr(0))
    cache.get(_csr(1))                       # different pattern
    s = cache.stats()
    assert (s.hits, s.misses) == (0, 2)


def test_cache_key_resolves_auto_and_defaults():
    cache = engine.PlanCache()
    a = _csr(2, npr=(0, 4))                  # short rows → heuristic: merge
    assert Heuristic().choose(a) == "merge"
    p1 = cache.get(a, PlanPolicy(method="auto"))
    p2 = cache.get(a, PlanPolicy(method="merge",
                                 t=merge_spmm.default_t(a.m, a.nnz_pad)))
    assert p1 is p2 and cache.stats().hits == 1


def test_cache_lru_eviction():
    cache = engine.PlanCache(maxsize=2)
    a0, a1, a2 = _csr(0), _csr(1), _csr(2)
    cache.get(a0)
    cache.get(a1)
    cache.get(a2)                            # evicts a0
    assert cache.stats().evictions == 1
    cache.get(a1)                            # still resident
    assert cache.stats().hits == 1
    cache.get(a0)                            # rebuilt
    assert cache.stats().misses == 4


def test_alias_map_is_bounded():
    """Cycling distinct raw request keys (fresh heuristic objects with new
    thresholds) must not grow the alias map without bound — the long-lived
    server leak of ISSUE 3."""
    cache = engine.PlanCache(maxsize=4, alias_maxsize=8)
    a = _csr(20, npr=(0, 4))                 # short rows: merge either way
    for i in range(50):
        cache.get(a, PlanPolicy(heuristic=Heuristic(threshold=100.0 + i)))
    s = cache.stats()
    assert s.misses == 1, "distinct thresholds resolved to the same plan"
    assert len(cache._aliases) <= 8
    assert s.aliases <= 8
    assert s.alias_evictions == 50 - 8
    # aliased fast path still hits after evictions
    cache.get(a, PlanPolicy(heuristic=Heuristic(threshold=149.0)))
    assert cache.stats().hits == 50


def test_alias_map_pruned_with_canonical_eviction():
    cache = engine.PlanCache(maxsize=1)
    a0, a1 = _csr(21), _csr(22)
    cache.get(a0)
    cache.get(a1)                            # evicts a0's plan
    assert cache.stats().evictions == 1
    assert all(c in cache._entries for c in cache._aliases.values())


def test_fingerprint_is_pattern_identity():
    a = _csr(3)
    assert pattern_fingerprint(a) == pattern_fingerprint(_with_vals(a, 9))
    assert pattern_fingerprint(a) != pattern_fingerprint(_csr(4))


# ------------------------------------------------- the no-replan criterion ---


def test_jitted_loop_never_replans(monkeypatch):
    """plan_merge/plan_rowsplit run at most once per pattern — zero times
    inside the jitted loop, because the plan was built at layer-build."""
    calls = {"merge": 0, "rowsplit": 0}
    orig_m = merge_spmm.plan_merge_structure
    orig_r = rowsplit_spmm.plan_rowsplit_structure
    monkeypatch.setattr(
        merge_spmm, "plan_merge_structure",
        lambda *a, **k: calls.__setitem__("merge", calls["merge"] + 1)
        or orig_m(*a, **k))
    monkeypatch.setattr(
        rowsplit_spmm, "plan_rowsplit_structure",
        lambda *a, **k: calls.__setitem__("rowsplit", calls["rowsplit"] + 1)
        or orig_r(*a, **k))

    cache = engine.PlanCache()
    a = _csr(5, m=24, k=16)
    plan = cache.get(a, PlanPolicy(method="rowsplit"))
    built = dict(calls)
    assert built["rowsplit"] == 1

    @jax.jit
    def step(p, vals, b):
        return execute_plan(p, vals, b, ExecutionConfig(impl="xla"))

    b = jax.random.normal(jax.random.PRNGKey(0), (a.k, 8))
    for i in range(4):                       # fresh values every step
        step(plan, jax.random.normal(jax.random.PRNGKey(i),
                                     a.vals.shape), b)
    assert calls == built, "jitted loop replanned"
    assert cache.get(_with_vals(a, 1),
                     PlanPolicy(method="rowsplit")) is plan
    assert calls == built, "cache hit replanned"


def test_sparse_linear_carries_plan_through_jit():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
    sl = SparseLinear.from_dense(w, 0.3)
    x = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)

    @jax.jit
    def f(layer, xx):
        return layer(xx, ExecutionConfig(impl="xla"))

    misses0 = engine.cache_stats().misses
    y1 = f(sl, x)
    y2 = f(sl, 2.0 * x)
    assert engine.cache_stats().misses == misses0
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


def test_ensure_spmm_plans_roundtrip():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    sl = SparseLinear.from_dense(w, 0.5)
    stripped = {"mlp": {"w1": dataclasses.replace(sl, plan=None)},
                "dense": jnp.ones((3, 3))}
    fixed = R.ensure_spmm_plans(stripped)
    assert fixed["mlp"]["w1"].plan is not None
    assert fixed["mlp"]["w1"].plan.meta == sl.plan.meta
    np.testing.assert_array_equal(np.asarray(fixed["dense"]), np.ones((3, 3)))


# -------------------------------------------------------- plan execution ---


@pytest.mark.parametrize("method", ["merge", "rowsplit"])
def test_execute_plan_matches_dense(method):
    a = _csr(6, m=40, k=32, npr=(0, 10))
    b = jax.random.normal(jax.random.PRNGKey(1), (a.k, 20))
    plan = build_plan(a, method=method)
    want = np.asarray(ref.spmm_dense_ref(a, b))
    for impl in ("xla", "pallas"):
        got = execute_plan(plan, a.vals, b, ExecutionConfig(impl=impl))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)


def test_spmm_routes_through_engine_cache():
    a = _csr(7)
    b = jax.random.normal(jax.random.PRNGKey(2), (a.k, 8))
    engine.clear_cache()
    spmm(a, b, exec=ExecutionConfig(impl="xla"))
    misses = engine.cache_stats().misses
    assert misses == 1
    spmm(_with_vals(a, 3), b,
         exec=ExecutionConfig(impl="xla"))    # same pattern → no rebuild
    s = engine.cache_stats()
    assert (s.misses, s.hits) == (misses, 1)


# ------------------------------------------------------- trace ergonomics ---


def test_get_plan_under_trace_raises():
    a = _csr(8)
    with pytest.raises(ValueError, match="outside jit"):
        jax.jit(lambda aa: engine.get_plan(aa))(a)


def test_heuristic_under_trace_raises():
    a = _csr(9)
    with pytest.raises(ValueError, match="plan-build time"):
        jax.jit(lambda aa: jnp.zeros(())
                if Heuristic().choose(aa) else jnp.ones(()))(a)


def test_spmm_auto_under_trace_raises():
    a = _csr(10)
    b = jax.random.normal(jax.random.PRNGKey(3), (a.k, 8))
    with pytest.raises(ValueError, match="plan-build time"):
        jax.jit(spmm)(a, b)


def test_rowsplit_under_trace_error_mentions_plan():
    a = _csr(11)
    b = jax.random.normal(jax.random.PRNGKey(4), (a.k, 8))
    with pytest.raises(ValueError, match="SpmmPlan"):
        jax.jit(lambda aa, bb: ops.rowsplit_spmm(aa, bb))(a, b)


def test_rowsplit_l_pad_lives_in_plan():
    """Under trace, the plan supplies the static l_pad — no argument."""
    a = _csr(12, npr=(0, 6))
    b = jax.random.normal(jax.random.PRNGKey(5), (a.k, 8))
    plan = build_plan(a, method="rowsplit")    # derives l_pad statically
    assert plan.l_pad == int(np.diff(np.asarray(a.row_ptr)).max())
    got = jax.jit(lambda p, v, bb: execute_plan(
        p, v, bb, ExecutionConfig(impl="xla")))(
        plan, a.vals, b)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.spmm_dense_ref(a, b)),
                               rtol=2e-5, atol=2e-5)


# -------------------------------------------------------- sharded plans ---


def test_sharded_plans_land_as_distinct_entries():
    """One sharded request = one entry per shard (keyed on the shard's own
    fingerprint) + one entry for the assembled ShardedSpmmPlan."""
    from repro.core import PlanPolicy, ShardSpec
    from repro.distributed.spmm import shard_csr_by_nnz

    cache = engine.PlanCache()
    a = _csr(20, m=40)
    plan = cache.get(a, PlanPolicy(method="merge", shards=ShardSpec(n=4)))
    s = cache.stats()
    assert (s.hits, s.misses, s.size) == (0, 5, 5)
    fps = {pattern_fingerprint(c) for c in shard_csr_by_nnz(a, 4).csrs}
    assert len(fps) == len(set(fps) | {pattern_fingerprint(a)}) - 1
    # a repeat of the same request is one O(1) hit on the sharded entry
    again = cache.get(a, PlanPolicy(method="merge", shards=ShardSpec(n=4)))
    assert again is plan
    assert cache.stats().hits == 1


def test_reshard_different_mesh_size_does_not_poison_cache():
    from repro.core import PlanPolicy, ShardSpec

    cache = engine.PlanCache()
    a = _csr(21, m=40)
    p4 = cache.get(a, PlanPolicy(method="merge", shards=ShardSpec(n=4)))
    p2 = cache.get(a, PlanPolicy(method="merge", shards=ShardSpec(n=2)))
    assert p4 is not p2
    assert p4.meta.n_shards == 4 and p2.meta.n_shards == 2
    # both shard layouts stay live and hit independently
    assert cache.get(a, PlanPolicy(method="merge",
                                   shards=ShardSpec(n=4))) is p4
    assert cache.get(a, PlanPolicy(method="merge",
                                   shards=ShardSpec(n=2))) is p2
    # and the unsharded plan is yet another entry, untouched by either
    p1 = cache.get(a, PlanPolicy(method="merge"))
    assert p1 is not p4 and p1 is not p2


def test_sharded_and_local_entries_share_one_lru():
    """Sharded entries participate in the same LRU/eviction accounting."""
    from repro.core import PlanPolicy, ShardSpec

    cache = engine.PlanCache(maxsize=3)
    a = _csr(22, m=24)
    cache.get(a, PlanPolicy(method="merge", shards=ShardSpec(n=2)))
    s = cache.stats()
    assert s.misses == 3 and s.size == 3 and s.evictions == 0
    cache.get(_csr(23), PlanPolicy(method="merge"))
    assert cache.stats().evictions == 1


def test_policy_shards_conflict_guards():
    from repro.core import PlanPolicy, ShardSpec

    a = _csr(24)
    b = jax.random.normal(jax.random.PRNGKey(1), (a.k, 4))
    plan = build_plan(a, method="merge")
    # an unsharded plan refuses a sharded policy override
    with pytest.raises(ValueError, match="unsharded"):
        spmm(a, b, PlanPolicy(shards=2), plan=plan)
    # a sharded plan refuses mismatched shard counts / dims / methods
    sharded = engine.get_plan(a, PlanPolicy(method="merge",
                                            shards=ShardSpec(n=2)))
    with pytest.raises(ValueError, match="shards n=4"):
        spmm(a, b, PlanPolicy(shards=ShardSpec(n=4)), plan=sharded)
    with pytest.raises(ValueError, match="dim"):
        spmm(a, b, PlanPolicy(shards=ShardSpec(n=2, dim="cols")),
             plan=sharded)
    with pytest.raises(ValueError, match="method"):
        spmm(a, b, PlanPolicy(method="rowsplit"), plan=sharded)
    # agreeing overrides pass through
    got = spmm(a, b, PlanPolicy(method="merge",
                                shards=ShardSpec(n=2)), plan=sharded)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.spmm_dense_ref(a, b)),
                               rtol=2e-5, atol=2e-5)
    # resolve() on a sharded policy is a per-shard decision — guarded
    with pytest.raises(ValueError, match="per shard"):
        PlanPolicy(shards=2).resolve(a)
    # the inline path cannot shard
    with pytest.raises(ValueError, match="inline"):
        spmm(a, b, PlanPolicy(method="merge", shards=2), plan="inline")
    # ShardSpec itself validates its fields
    with pytest.raises(ValueError, match="dim"):
        ShardSpec(n=2, dim="diag")
    with pytest.raises(ValueError, match="n= "):
        ShardSpec()


def test_ensure_spmm_plans_shards_leaves():
    from repro.core import PlanPolicy, ShardSpec, SparseMatrix
    from repro.distributed.spmm import ShardedSpmmPlan

    a = _csr(25, m=40)
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 12))
    tree = {"mtx": SparseMatrix.from_csr(a),
            "layer": SparseLinear.from_dense(w, 0.25)}
    planned = R.ensure_spmm_plans(tree, policy=PlanPolicy(shards=2))
    assert isinstance(planned["mtx"].spmm_plan, ShardedSpmmPlan)
    assert isinstance(planned["layer"].plan, ShardedSpmmPlan)
    assert planned["layer"].method in ("merge", "rowsplit", "mixed")
    # replan with no policy replays the shard layout (plan_like path)
    again = R.ensure_spmm_plans(planned)
    assert isinstance(again["mtx"].spmm_plan, ShardedSpmmPlan)
    assert again["mtx"].spmm_plan.meta.n_shards == 2
