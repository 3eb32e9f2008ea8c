"""Observability subsystem: tracing, metrics, byte models, engine wiring.

Covers the legs of ``repro.obs`` (ring-buffer tracer + Chrome export,
profiler annotations, the typed metrics registry, the compulsory-bytes
model; the scope vocabulary and its device-op join are in
``test_scopes.py``) and — more importantly — the *engine integration*:
``PlanPolicy.resolve`` records which ladder rung fired, ``PlanCache``
counts hits/misses/evictions through the registry (back-compat
``stats()`` preserved), plan builds time each step, ``execute_plan``
emits dispatch events under tracing, and sharded builds trace the
per-shard method mix + nnz imbalance.  With neither the ring nor a
profiler recording, a span is the shared null span and records nothing.

The sharded-trace tests need 8 devices; like ``test_distributed_spmm``
they are re-run in a forced 8-device subprocess when the parent came up
single-device, so they execute everywhere.
"""
import glob
import json
import os
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (ExecutionConfig, PlanPolicy, ShardSpec, build_plan,
                        execute_plan, random_csr)
from repro.core.plan import pattern_fingerprint
from repro.engine import PlanCache
from repro.matrices import compute_stats
from repro.obs import validate as obs_validate
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import _NULL_SPAN
from repro.tune.db import TuneDB, TuneRecord

NDEV = 8
IN_CHILD = bool(os.environ.get("_REPRO_FORCED_CHILD"))
needs_devices = pytest.mark.skipif(
    jax.device_count() < NDEV,
    reason=f"needs {NDEV} devices (covered by the forced-subprocess "
    "wrapper / make test-sharded)")

_XLA = ExecutionConfig(impl="xla")


def _csr(seed=0, m=24, k=16, npr=(0, 6)):
    return random_csr(jax.random.PRNGKey(seed), m, k, nnz_per_row=npr)


def _b(a, n=5, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (a.k, n))


# ------------------------------------------------------------- metrics ---


def test_counter_labels_and_values():
    reg = MetricsRegistry()
    fam = reg.counter("c", "help", labels=("x",))
    fam.labels(x="a").inc()
    fam.labels(x="a").inc(3)
    fam.labels(x="b").inc()
    assert fam.labels(x="a").value == 4
    assert fam.labels(x="b").value == 1
    assert {tuple(c.labels.items()) for c in fam.children()} == \
        {(("x", "a"),), (("x", "b"),)}


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("g")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.value == 3.0


def test_histogram_snapshot_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    s = reg.get("h").labels().snapshot()
    assert s["count"] == 100 and s["sum"] == 5050.0
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == pytest.approx(50.5)
    assert s["p95"] == pytest.approx(95.05)
    # empty histogram: count-0 snapshot, NaN percentile
    h2 = reg.histogram("h2")
    assert reg.get("h2").snapshot()["values"] == []
    h2.observe(7.0)
    assert reg.get("h2").labels().percentile(50) == 7.0


def test_registry_declare_idempotent_and_conflicting():
    reg = MetricsRegistry()
    a = reg.counter("n", "first", labels=("l",))
    b = reg.counter("n", "second", labels=("l",))
    assert a is b
    with pytest.raises(ValueError, match="already declared"):
        reg.gauge("n", labels=("l",))
    with pytest.raises(ValueError, match="already declared"):
        reg.counter("n", labels=("other",))


def test_label_schema_enforced():
    reg = MetricsRegistry()
    fam = reg.counter("n", labels=("x", "y"))
    with pytest.raises(ValueError, match="takes labels"):
        fam.labels(x="a")
    with pytest.raises(ValueError, match="bind them"):
        fam.inc()                      # unlabeled convenience needs no labels


def test_counter_concurrent_increments_lose_nothing():
    reg = MetricsRegistry()
    c = reg.counter("c", labels=("who",)).labels(who="race")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(500)])
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8 * 500


def test_report_and_dump_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("hits", "cache hits", labels=("cache",)) \
        .labels(cache="c0").inc(2)
    reg.histogram("lat").observe(10.0)
    text = reg.report()
    assert "hits{cache=c0} 2" in text
    assert "lat count=1" in text
    path = reg.dump(str(tmp_path / "m.json"), extra={"run": "t"})
    doc = json.loads(open(path).read())
    assert doc["schema"] == 1 and doc["run"] == "t"
    assert doc["metrics"]["hits"]["values"][0]["value"] == 2
    assert obs_validate.validate_metrics(path, require_names=("hits",)) == []


# -------------------------------------------------------------- tracer ---


def test_span_event_ring_and_chrome_export(tmp_path):
    with obs.tracing(capacity=64) as tr:
        with obs.span("work", cat="plan", m=3) as sp:
            sp.set(rung="exact")
        obs.event("tick", cat="cache", hit=True)
        evs = tr.events()
        assert [e["ph"] for e in evs] == ["X", "i"]
        assert evs[0]["args"] == {"m": 3, "rung": "exact"}
        assert evs[0]["dur"] >= 0
        assert tr.events(cat="cache", name="tick")
        path = tr.export(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    assert {e["name"] for e in doc["traceEvents"]} == {"work", "tick"}
    assert obs_validate.validate_trace(
        path, require_cats=("plan", "cache")) == []


def test_ring_capacity_drops_oldest():
    with obs.tracing(capacity=4) as tr:
        for i in range(6):
            obs.event(f"e{i}")
        assert len(tr) == 4
        assert tr.dropped == 2
        assert [e["name"] for e in tr.events()] == ["e2", "e3", "e4", "e5"]
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0


def test_disabled_path_is_noop():
    assert not obs.is_enabled()        # tier-1 runs untraced
    assert obs.span("x", cat="plan") is _NULL_SPAN
    with obs.span("x") as sp:
        sp.set(anything=1)             # swallowed
    obs.event("x")                     # no tracer, no error
    before = obs.get_tracer()
    with obs.tracing() as tr:
        assert obs.is_enabled() and obs.get_tracer() is tr
        obs.event("inner")
    assert not obs.is_enabled() and obs.get_tracer() is before


def test_tracing_nests_and_restores():
    with obs.tracing() as outer:
        obs.event("a")
        with obs.tracing() as inner:
            obs.event("b")
        assert [e["name"] for e in inner.events()] == ["b"]
        assert obs.get_tracer() is outer
        obs.event("c")
        assert [e["name"] for e in outer.events()] == ["a", "c"]


# ----------------------------------------------------- engine: PlanCache ---


def test_plan_cache_metrics_and_backcompat_stats():
    cache = PlanCache(maxsize=2, name="t-obs-cache")
    a1, a2, a3 = _csr(1), _csr(2), _csr(3)
    pol = PlanPolicy(method="merge")
    cache.get(a1, pol)
    cache.get(a1, pol)                 # hit
    cache.get(a2, pol)
    cache.get(a3, pol)                 # evicts a1
    s = cache.stats()
    assert (s.hits, s.misses, s.evictions) == (1, 3, 1)
    fam = obs.registry.get("plan_cache_events_total")
    assert fam.labels(cache="t-obs-cache", event="hit").value == 1
    assert fam.labels(cache="t-obs-cache", event="miss").value == 3
    assert obs.registry.get("plan_cache_size") \
        .labels(cache="t-obs-cache").value == 2
    cache.clear()
    s = cache.stats()
    assert (s.hits, s.misses, s.evictions) == (0, 0, 0)


def test_plan_cache_events_traced():
    cache = PlanCache(name="t-obs-trace")
    a = _csr(4)
    with obs.tracing() as tr:
        cache.get(a, PlanPolicy(method="merge"))
        cache.get(a, PlanPolicy(method="merge"))
    miss = tr.events(cat="cache", name="cache.miss")
    hit = tr.events(cat="cache", name="cache.hit")
    assert len(miss) == 1 and len(hit) == 1
    assert miss[0]["args"]["cache"] == "t-obs-trace"
    assert hit[0]["args"]["method"] == "merge"
    assert tr.events(cat="plan", name="plan.build")  # span around build


def _step_counts() -> dict:
    fam = obs.registry.get("plan_build_step_seconds")
    return {} if fam is None else {
        (c.labels["method"], c.labels["step"]): c.count
        for c in fam.children()}


def _delta(before, after) -> dict:
    return {k: n - before.get(k, 0) for k, n in after.items()
            if n != before.get(k, 0)}


def test_plan_build_steps_observed_once_and_nested():
    cache = PlanCache(name="t-obs-steps")
    a = _csr(15)
    before = _step_counts()
    with obs.tracing() as tr:
        plan = cache.get(a, PlanPolicy())                # method "auto"
    steps = ("fingerprint", "resolve", "structure", "row_ids")
    method = plan.meta.method                 # the resolved method labels
    assert method != "auto"                   # every step but the hash
    assert _delta(before, _step_counts()) == {
        ("" if s == "fingerprint" else method, s): 1 for s in steps}
    build, = tr.events(cat="plan", name="plan.build")
    end = build["ts"] + build["dur"]
    span = {s: tr.events(cat="plan", name=f"plan.step.{s}") for s in steps}
    assert all(len(v) == 1 for v in span.values())
    # The hash keys the lookup and resolution picks the method, both
    # before the build; the build's own steps nest inside it.
    for s in ("fingerprint", "resolve"):
        assert span[s][0]["ts"] + span[s][0]["dur"] <= build["ts"]
    for s in ("structure", "row_ids"):
        ev = span[s][0]
        assert build["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= end
    fam = obs.registry.get("plan_build_step_seconds")
    assert all(fam.labels(method="" if s == "fingerprint" else method,
                          step=s).sum > 0 for s in steps)


def test_plan_build_steps_always_on_and_only_in_builds():
    assert not obs.is_enabled()
    cache = PlanCache(name="t-obs-steps-off")
    a = _csr(16)
    before = _step_counts()
    cache.get(a, PlanPolicy(method="merge"))             # a build
    built = _step_counts()
    assert _delta(before, built) == {
        ("", "fingerprint"): 1, ("merge", "resolve"): 1,
        ("merge", "structure"): 1, ("merge", "row_ids"): 1}
    cache.get(a, PlanPolicy(method="merge"))             # a hit: nothing
    assert _step_counts() == built
    twin = type(a)(a.row_ptr, a.col_ind, a.vals, a.shape)
    cache.get(twin, PlanPolicy(method="merge"))          # a new object:
    assert _delta(built, _step_counts()) == {            # hashed, no build
        ("", "fingerprint"): 1}


def test_span_reaches_a_profiler_capture_with_the_ring_off(tmp_path):
    from jax.profiler import ProfileData
    assert not obs.is_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("obs.test.annotated", cat="plan", m=1):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert "obs.test.annotated" in host
    tr = obs.get_tracer()
    assert tr is None or not tr.events(name="obs.test.annotated")
    # No profiler recording: back to the shared null span.
    assert obs.span("obs.test.annotated") is _NULL_SPAN


# ------------------------------------------------ resolve: ladder rungs ---


def _rung_value(rung, method):
    return obs.registry.get("plan_resolve_total") \
        .labels(rung=rung, method=method).value


def _resolve_delta(rung, method, policy, a):
    before = _rung_value(rung, method)
    r = policy.resolve(a)
    return r, _rung_value(rung, method) - before


def test_resolve_rung_explicit_and_analytic():
    a = _csr(5)
    r, d = _resolve_delta("explicit", "rowsplit",
                          PlanPolicy(method="rowsplit"), a)
    assert r.method == "rowsplit" and d == 1
    # tunedb=None opts out of the ladder: analytic heuristic decides
    r, d = _resolve_delta("analytic", None, PlanPolicy(tunedb=None), a)
    assert d == 0 or True              # method unknown a priori; check below
    assert _rung_value("analytic", r.method) >= 1


def _db_with(a, method="merge", fingerprint=None, l_pad=None):
    s = compute_stats(a)
    db = TuneDB(backend="test")
    db.record(fingerprint or pattern_fingerprint(a),
              TuneRecord(method=method, merge_us=1.0, rowsplit_us=2.0,
                         m=s.m, k=s.k, d=s.d, cv=s.cv, n=8, l_pad=l_pad))
    return db


def test_resolve_rung_exact():
    a = _csr(6)
    r, d = _resolve_delta("exact", "merge",
                          PlanPolicy(tunedb=_db_with(a)), a)
    assert r.method == "merge" and d == 1


def test_resolve_rung_class():
    a = _csr(7)
    # same class signature (stats copied from `a`), different fingerprint:
    # the exact rung misses, the binned class rung hits.
    db = _db_with(a, fingerprint="some-other-pattern")
    r, d = _resolve_delta("class", "merge", PlanPolicy(tunedb=db), a)
    assert r.method == "merge" and d == 1


def test_resolve_rung_calibrated():
    a = _csr(8)
    # non-None TuneDB with no matching record: the ladder bottoms out in
    # the DB-calibrated threshold heuristic.
    db = TuneDB(backend="test")
    before = sum(c.value
                 for c in obs.registry.get("plan_resolve_total").children()
                 if c.labels["rung"] == "calibrated")
    PlanPolicy(tunedb=db).resolve(a)
    after = sum(c.value
                for c in obs.registry.get("plan_resolve_total").children()
                if c.labels["rung"] == "calibrated")
    assert after - before == 1


def test_resolve_fallback_traced():
    """Exact record replays rowgroup, caller's l_pad rejects it: the
    analytic fallback fires and the trace event carries fallback=True."""
    a = _csr(9)
    lmax = int(np.diff(np.asarray(a.row_ptr)).max())
    db = _db_with(a, method="rowgroup")
    with obs.tracing() as tr:
        r = PlanPolicy(tunedb=db, l_pad=lmax + 2).resolve(a)
    assert r.method in ("merge", "rowsplit")
    evs = tr.events(cat="plan", name="plan.resolve")
    assert len(evs) == 1
    assert evs[0]["args"]["fallback"] is True
    assert evs[0]["args"]["rung"] == "analytic"


def test_resolve_trace_event_args():
    a = _csr(10)
    with obs.tracing() as tr:
        PlanPolicy(method="merge").resolve(a)
    ev, = tr.events(cat="plan", name="plan.resolve")
    assert ev["args"]["rung"] == "explicit"
    assert ev["args"]["method"] == "merge"
    assert ev["args"]["m"] == a.m and ev["args"]["k"] == a.k
    assert ev["args"]["nnz_pad"] == a.nnz_pad


# --------------------------------------------------- dispatch + execute ---


def test_dispatch_event_and_execute_counter():
    a = _csr(11)
    plan = build_plan(a, method="merge", with_transpose=False)
    b = _b(a)
    fam = obs.registry.get("plan_execute_total")
    with obs.tracing() as tr:
        execute_plan(plan, a.vals, b, _XLA)
    ev = tr.events(cat="dispatch", name="dispatch")
    assert len(ev) == 1
    args = ev[0]["args"]
    assert args["method"] == "merge" and args["impl"] == "xla"
    assert args["n"] == b.shape[-1]
    label = f"merge:{a.m}x{a.k}:nnz{a.nnz_pad}"
    assert fam.labels(plan=label, impl="xla").value >= 1
    # per-execute accounting is gated on tracing: untraced calls add nothing
    before = fam.labels(plan=label, impl="xla").value
    execute_plan(plan, a.vals, b, _XLA)
    assert fam.labels(plan=label, impl="xla").value == before


# ------------------------------------------------------------- roofline ---


def test_spmm_min_bytes_model():
    assert obs.spmm_min_bytes(4, 8, 2, 10) == 10 * 8 + 8 * 2 * 4 + 4 * 2 * 4
    assert obs.spmm_flops(10, 2) == 40.0


def test_plan_min_bytes_dtype_scaling():
    a = _csr(12)
    plan = build_plan(a, method="merge", with_transpose=False)
    f32 = obs.plan_min_bytes(plan.meta, 16)
    bf16 = obs.plan_min_bytes(plan.meta, 16, val_dtype="bfloat16")
    assert f32 > bf16                  # half-width vals, B, and C
    m, k = plan.meta.shape
    nnz = plan.meta.nnz_pad
    assert f32 == obs.spmm_min_bytes(m, k, 16, nnz)
    assert bf16 == obs.spmm_min_bytes(m, k, 16, nnz, val_bytes=2,
                                      out_bytes=2)


def test_obs_report_combines_legs():
    a = _csr(14)
    PlanPolicy(method="merge").resolve(a)
    with obs.tracing() as tr:
        obs.event("tick")
        text = obs.report()
    assert "resolution ladder" in text and "explicit=" in text
    assert "plan_resolve_total{rung=explicit,method=merge}" in text
    assert "== trace == 1 events buffered" in text
    assert len(tr) == 1
    assert "roof" not in text          # no second roofline beside bench/


# ------------------------------------------------------------- validate ---


def test_validate_trace_rejects_garbage(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("not json")
    assert obs_validate.validate_trace(str(p))
    p.write_text(json.dumps({"events": []}))
    assert "traceEvents" in obs_validate.validate_trace(str(p))[0]
    p.write_text(json.dumps({"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 1}]}))
    probs = obs_validate.validate_trace(str(p))
    assert any("without numeric 'dur'" in x for x in probs)
    p.write_text(json.dumps({"traceEvents": [
        {"name": "x", "cat": "plan", "ph": "i", "ts": 0, "pid": 1,
         "tid": 1}]}))
    assert obs_validate.validate_trace(str(p)) == []
    probs = obs_validate.validate_trace(str(p), require_cats=("dispatch",))
    assert any("required category 'dispatch'" in x for x in probs)
    assert obs_validate.validate_trace(str(p), min_events=2)


def test_validate_metrics_rejects_garbage(tmp_path):
    p = tmp_path / "m.json"
    p.write_text(json.dumps({"schema": 2, "metrics": {}}))
    assert "schema" in obs_validate.validate_metrics(str(p))[0]
    p.write_text(json.dumps({"schema": 1, "metrics": {}}))
    assert obs_validate.validate_metrics(str(p))
    p.write_text(json.dumps(
        {"schema": 1, "metrics": {"c": {"type": "counter", "values": []}}}))
    assert obs_validate.validate_metrics(str(p)) == []
    assert obs_validate.validate_metrics(str(p), require_names=("absent",))


def test_validate_cli_exit_codes(tmp_path, capsys):
    with obs.tracing() as tr:
        obs.event("x", cat="plan")
        trace = tr.export(str(tmp_path / "t.json"))
    metrics = obs.registry.dump(str(tmp_path / "m.json"))
    assert obs_validate.main(["--trace", trace, "--metrics", metrics,
                              "--require-cats", "plan"]) == 0
    assert obs_validate.main(["--trace", trace,
                              "--require-cats", "nonexistent"]) == 1


# ------------------------------------------------- benchmarks stay wired ---


def test_bench_modules_all_registered():
    from benchmarks import run as bench_run
    assert bench_run.check_registration() == []
    # drop one module: the check names the missing stem
    mods = bench_run._mods()
    missing = bench_run.check_registration(mods[:-1])
    assert mods[-1][1].__name__.rsplit(".", 1)[-1] in missing


def test_timeit_result_surface():
    from repro.tune import TimingResult, timeit
    t = timeit(lambda: None, warmup=0, repeat=5)
    assert isinstance(t, TimingResult) and isinstance(t, float)
    assert len(t.samples) == 5
    assert t.min <= t.p50 <= t.p95 <= t.max
    assert float(t) == t.median and t.cv >= 0.0
    # benchmarks.common re-exports the same objects
    from benchmarks import common
    assert common.timeit is timeit and common.TimingResult is TimingResult


# -------------------------------------------- sharded trace (8 devices) ---


@needs_devices
def test_sharded_build_and_execute_traced():
    a = _csr(20, m=64, k=32, npr=(0, 9))
    b = _b(a, n=6)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:NDEV]), ("data",))
    from repro.distributed.spmm import build_sharded_plan, execute_sharded
    with obs.tracing() as tr:
        plan = build_sharded_plan(
            a, PlanPolicy(method="merge",
                          shards=ShardSpec(n=NDEV, mesh=mesh)))
        execute_sharded(plan, a.vals, b, _XLA)
    sp, = tr.events(cat="plan", name="plan.build_sharded")
    assert sp["args"]["n_shards"] == NDEV
    assert len(sp["args"]["methods"]) == NDEV
    assert sp["args"]["nnz_imbalance"] >= 1.0
    assert len(sp["args"]["nnz_per_shard"]) == NDEV
    asm, = tr.events(cat="plan", name="plan.sharded_assembled")
    assert asm["args"]["methods"] == ["merge"] * NDEV
    d, = tr.events(cat="dispatch", name="dispatch.sharded")
    assert d["args"]["path"] == "spmd" if asm["args"]["uniform"] else "loop"
    assert d["args"]["n_shards"] == NDEV
    gauge = obs.registry.get("shard_nnz_imbalance").labels(dim="rows")
    assert gauge.value == pytest.approx(sp["args"]["nnz_imbalance"],
                                        abs=1e-3)


@pytest.mark.skipif(jax.device_count() >= NDEV or IN_CHILD,
                    reason="already running with a forced multi-device "
                    "substrate")
def test_sharded_trace_in_forced_subprocess(forced_device_run):
    res = forced_device_run(
        "tests/test_obs.py::test_sharded_build_and_execute_traced", NDEV)
    assert res.returncode == 0, (
        f"forced {NDEV}-device run failed:\n{res.stdout}\n{res.stderr}")
    assert " passed" in res.stdout
