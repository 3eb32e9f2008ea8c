"""LRU cache of SpmmPlans keyed on sparsity-pattern identity.

The amortization layer of the plan-once/execute-many engine: a pruned
weight's pattern is frozen for the lifetime of the model, so every plan
derived from it — forward chunk/ELL structure, heuristic decision,
transpose plan — is built at most once per pattern and shared by every
layer, step, and restart that presents the same mask.

Keys are *content* fingerprints of (row_ptr, col_ind) plus the build
configuration, not object identity — re-pruning with the same mask,
checkpoint restore, or two layers tied to one mask all hit.  Counters
(hits/misses/evictions) are exposed for tests and ops dashboards; the
acceptance criterion "plans are built at most once per pattern in a jitted
loop" is asserted against them in ``tests/test_engine.py``.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
from collections import OrderedDict

from repro import obs as _obs
from repro.analysis import _flags as _verify_flags
from repro.core.config import (DEFAULT_TUNEDB, PlanPolicy, _UNSET,
                               _warn_deprecated)
from repro.core.csr import CSR
from repro.core.plan import (SpmmPlan, build_plan, build_step,
                             pattern_fingerprint)
from repro.obs import trace as _trace

DEFAULT_MAXSIZE = 256

# Cache counters live on the global metrics registry, one labeled child
# per (cache instance, event).  Each child increments under its own lock,
# so executors sharing a cache can never lose counts; ``stats()`` keeps
# presenting them as the historical CacheStats view.
_cache_events = _obs.registry.counter(
    "plan_cache_events_total", "PlanCache events by cache instance",
    labels=("cache", "event"))
_cache_size = _obs.registry.gauge(
    "plan_cache_size", "live entries per PlanCache", labels=("cache",))
_cache_alias_size = _obs.registry.gauge(
    "plan_cache_aliases", "live alias-map entries per PlanCache",
    labels=("cache",))

_cache_ids = itertools.count()

# Legacy sentinel: "no tunedb argument given — use the process default".
_USE_DEFAULT = DEFAULT_TUNEDB


def _verify_hit(plan, a: CSR) -> None:
    """REPRO_VERIFY_PLANS debug hook on cache hits: misses verify inside
    ``build_plan`` itself, but a hit serves a stored plan keyed by content
    fingerprint — re-verify it against the CSR actually presented, so a
    fingerprint collision or stale alias fails here, not in a kernel."""
    from repro.analysis.planlint import check_plan
    check_plan(plan, a)

# Process-wide empirical tuning database (repro.tune.TuneDB).  When set,
# every "auto" plan request resolves its method through measurements
# (exact pattern -> pattern class -> calibrated threshold) instead of the
# paper's fixed K40c threshold.  Host-side only: consulted at plan build,
# never inside jit.
_default_tunedb = None


def set_tunedb(db) -> None:
    """Install (or clear, with None) the process-default TuneDB."""
    global _default_tunedb
    _default_tunedb = db


def current_tunedb():
    return _default_tunedb


def load_tunedb(path, **kw):
    """Load a TuneDB from ``path`` and install it as the process default.

    Forgiving like ``TuneDB.load``: a corrupt/mismatched file installs an
    empty DB (with a warning), so plan building falls back to the
    analytic heuristic rather than crashing the launcher.
    """
    from repro.tune.db import TuneDB

    db = TuneDB.load(path, **kw)
    set_tunedb(db)
    return db


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    size: int = 0
    aliases: int = 0
    alias_evictions: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PlanCache:
    """Thread-safe LRU over ``build_plan`` results."""

    def __init__(self, maxsize: int = DEFAULT_MAXSIZE,
                 alias_maxsize: int | None = None,
                 name: str | None = None):
        self.maxsize = maxsize
        # The metric label distinguishing this instance's counters on the
        # global registry (the process-default cache is "default").
        self.name = name if name is not None else f"cache{next(_cache_ids)}"
        self._c_hit = _cache_events.labels(cache=self.name, event="hit")
        self._c_miss = _cache_events.labels(cache=self.name, event="miss")
        self._c_evict = _cache_events.labels(cache=self.name,
                                             event="eviction")
        self._c_alias_evict = _cache_events.labels(
            cache=self.name, event="alias_eviction")
        self._g_size = _cache_size.labels(cache=self.name)
        self._g_aliases = _cache_alias_size.labels(cache=self.name)
        # The alias map is its own (cheap, key-only) LRU: raw request keys
        # embed per-request objects' attributes (heuristic thresholds,
        # TuneDB digests), so a long-lived server cycling those would
        # otherwise grow it without bound even while the plan LRU stays
        # capped (ISSUE 3 satellite).  A few aliases per plan is the
        # steady state; 4x leaves room for method/param spellings.
        self.alias_maxsize = (4 * maxsize if alias_maxsize is None
                              else alias_maxsize)
        self._entries: OrderedDict[tuple, SpmmPlan] = OrderedDict()
        # raw (unresolved) request key -> canonical key, so a hit on a
        # repeated request skips resolve_static's host sync entirely.
        self._aliases: OrderedDict[tuple, tuple] = OrderedDict()
        self._lock = threading.Lock()

    def _alias_insert(self, raw: tuple, key: tuple) -> None:
        # Callers hold self._lock.
        self._aliases[raw] = key
        self._aliases.move_to_end(raw)
        while len(self._aliases) > self.alias_maxsize:
            self._aliases.popitem(last=False)
            self._c_alias_evict.inc()
        self._g_aliases.set(len(self._aliases))

    def get(self, a: CSR, policy: PlanPolicy | None = None, *,
            method=_UNSET, heuristic=_UNSET, t=_UNSET, tl=_UNSET,
            l_pad=_UNSET, with_transpose=_UNSET,
            tunedb=_USE_DEFAULT) -> SpmmPlan:
        """Cached ``build_plan`` — the engine's plan-once entry point.

        The request is a :class:`PlanPolicy` (the bare kwargs remain as a
        pre-v1 spelling and fold into one; mixing both raises).  Canonical
        keys pin down the static decisions through the same
        ``PlanPolicy.resolve`` that ``build_plan`` uses, so "auto" and its
        resolved form share one entry and key/plan can never disagree.
        A raw-request alias map makes repeated identical requests O(1):
        neither the heuristic's host read nor the l_pad scan reruns on a
        hit (the fingerprint itself is memoized per CSR object).

        ``policy.tunedb`` (default: the process-wide DB from
        ``set_tunedb``) resolves "auto" methods from measurements; its
        content digest is part of the raw key, so swapping databases can
        never serve a plan resolved against the old one (explicit
        ``tunedb=None`` opts out).
        """
        legacy = {k: v for k, v in dict(
            method=method, heuristic=heuristic, t=t, tl=tl, l_pad=l_pad,
            with_transpose=with_transpose).items() if v is not _UNSET}
        if tunedb is not _USE_DEFAULT:
            legacy["tunedb"] = tunedb
        if legacy:
            if policy is not None:
                raise ValueError(
                    "PlanCache.get: pass either policy= or the bare kwargs "
                    f"{sorted(legacy)}, not both")
            for k in legacy:
                _warn_deprecated(
                    f"PlanCache.get({k}=...)",
                    f"pass policy=PlanPolicy({k}=...) "
                    "(see README.md: Migrating to API v1)", stacklevel=3)
            policy = PlanPolicy(**legacy)
        elif policy is None:
            policy = PlanPolicy()
        if policy.shards is not None:
            return self._get_sharded(a, policy)
        db = policy.resolved_tunedb()
        if policy.method == "auto":
            hkey = (policy.heuristic.threshold
                    if policy.heuristic is not None else None,
                    db.digest() if db is not None else None)
        else:
            hkey = None
        raw = (pattern_fingerprint(a), a.shape, a.nnz_pad, policy.method,
               hkey, policy.t, policy.tl, policy.l_pad,
               policy.with_transpose)
        with self._lock:
            canonical = self._aliases.get(raw)
            plan = self._entries.get(canonical) if canonical else None
            if plan is not None:
                self._entries.move_to_end(canonical)
                self._aliases.move_to_end(raw)
                self._c_hit.inc()
                if _trace._enabled:
                    _trace.event("cache.hit", cat="cache", cache=self.name,
                                 alias=True, method=plan.meta.method)
                if _verify_flags.verify_plans:
                    _verify_hit(plan, a)
                return plan
        with build_step("resolve") as st:
            r = policy.resolve(a)
            st.method = r.method
        key = (raw[0], a.shape, a.nnz_pad, r.method, r.t, r.tl, r.l_pad,
               policy.with_transpose)
        with self._lock:
            plan = self._entries.get(key)
            if plan is not None:
                self._entries.move_to_end(key)
                self._alias_insert(raw, key)
                self._c_hit.inc()
                if _trace._enabled:
                    _trace.event("cache.hit", cat="cache", cache=self.name,
                                 alias=False, method=plan.meta.method)
                if _verify_flags.verify_plans:
                    _verify_hit(plan, a)
                return plan
        # Build outside the lock — plans are pure functions of the key.
        if _trace._enabled:
            _trace.event("cache.miss", cat="cache", cache=self.name,
                         method=r.method)
        with _trace.span("plan.build", cat="plan", method=r.method,
                         m=int(a.shape[0]), k=int(a.shape[1]),
                         nnz_pad=int(a.nnz_pad), t=r.t, tl=r.tl,
                         l_pad=r.l_pad):
            plan = build_plan(a, method=r.method, t=r.t, tl=r.tl,
                              l_pad=r.l_pad,
                              with_transpose=policy.with_transpose,
                              _resolved=r)
        with self._lock:
            self._c_miss.inc()
            self._entries[key] = plan
            self._entries.move_to_end(key)
            self._alias_insert(raw, key)
            while len(self._entries) > self.maxsize:
                evicted, _ = self._entries.popitem(last=False)
                self._aliases = OrderedDict(
                    (r, c) for r, c in self._aliases.items() if c != evicted)
                self._c_evict.inc()
                if _trace._enabled:
                    _trace.event("cache.eviction", cat="cache",
                                 cache=self.name)
            self._g_size.set(len(self._entries))
            self._g_aliases.set(len(self._aliases))
        return plan

    def _get_sharded(self, a: CSR, policy: PlanPolicy):
        """Cached sharded-plan build (``policy.shards`` set).

        The sharded plan is one cache entry keyed on the *global* pattern
        plus the full shard spec (count, dim, axis, mesh), while every
        per-shard local plan lands as its own entry keyed on the shard's
        fingerprint (``build_sharded_plan`` funnels them back through
        ``get``).  Because the shard spec is in the key, re-sharding the
        same matrix over a different mesh size builds a sibling entry —
        it can never poison, nor be served from, the old one.
        """
        spec = policy.shards
        db = policy.resolved_tunedb()
        if policy.method == "auto":
            hkey = (policy.heuristic.threshold
                    if policy.heuristic is not None else None,
                    db.digest() if db is not None else None)
        else:
            hkey = None
        key = (pattern_fingerprint(a), a.shape, a.nnz_pad, "sharded",
               spec.resolved_n(), spec.dim, spec.axis, spec.mesh,
               policy.method, hkey, policy.t, policy.tl, policy.l_pad,
               policy.with_transpose)
        with self._lock:
            plan = self._entries.get(key)
            if plan is not None:
                self._entries.move_to_end(key)
                self._c_hit.inc()
                if _trace._enabled:
                    _trace.event("cache.hit", cat="cache", cache=self.name,
                                 alias=False, sharded=True)
                if _verify_flags.verify_plans:
                    _verify_hit(plan, a)
                return plan
        # Build outside the lock; the per-shard plans recurse through
        # self.get (each takes the lock for its own entry).
        from repro.distributed.spmm import build_sharded_plan

        if _trace._enabled:
            _trace.event("cache.miss", cat="cache", cache=self.name,
                         sharded=True)
        plan = build_sharded_plan(a, policy, cache=self)
        with self._lock:
            self._c_miss.inc()
            self._entries[key] = plan
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                evicted, _ = self._entries.popitem(last=False)
                self._aliases = OrderedDict(
                    (r, c) for r, c in self._aliases.items() if c != evicted)
                self._c_evict.inc()
                if _trace._enabled:
                    _trace.event("cache.eviction", cat="cache",
                                 cache=self.name)
            self._g_size.set(len(self._entries))
        return plan

    # ------------------------------------------------------ maintenance ---

    def stats(self) -> CacheStats:
        """The historical attribute view, assembled from the registry's
        per-instance children (still the API tests and callers use)."""
        return CacheStats(
            hits=self._c_hit.value, misses=self._c_miss.value,
            evictions=self._c_evict.value,
            size=int(self._g_size.value),
            aliases=int(self._g_aliases.value),
            alias_evictions=self._c_alias_evict.value)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._aliases.clear()
            for c in (self._c_hit, self._c_miss, self._c_evict,
                      self._c_alias_evict, self._g_size, self._g_aliases):
                c.reset()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


_default_cache = PlanCache(name="default")


def default_cache() -> PlanCache:
    return _default_cache


def get_plan(a: CSR, policy: PlanPolicy | None = None, **kw) -> SpmmPlan:
    """Module-level convenience over the process-wide default cache."""
    return _default_cache.get(a, policy, **kw)


def cache_stats() -> CacheStats:
    return _default_cache.stats()


def clear_cache() -> None:
    _default_cache.clear()
