"""Device time by program scope (``bench/scopes.py``) on the recorded TPU
trace, with a hand-made scope map in place of the program's, and the
plan-build reader on the program's registry."""
import dataclasses
import os

import pytest

from bench import scopes, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@dataclasses.dataclass
class W:
    units: int


@dataclasses.dataclass
class S:
    compiled: object


@dataclasses.dataclass
class R:
    trace: object
    window: W
    system: S


# The recorded program's ops (``test_trace_reduce``), put in scopes by hand.
MAP = {
    "rowsplit_execute.1": "kernel", "copy.1": "kernel", "copy.2": "kernel",
    "fusion": "gather_vals", "pad_maximum_fusion": "gather_vals",
    "broadcast_clamp_fusion": "gather_vals", "reshape.5": "gather_vals",
    "reshape.6": "gather_vals", "compare_select_fusion": "gather_vals",
    "copy-start": "gather_vals", "copy-done": "gather_vals",
    "copy-start.1": "kernel", "copy-done.1": "kernel",
    "copy-start.2": "widen_b", "copy-done.2": "widen_b",
}


@pytest.fixture
def run(monkeypatch):
    import repro.obs
    monkeypatch.setattr(repro.obs, "device_scopes", lambda compiled: MAP,
                        raising=False)
    tr = trace_reduce.load(os.path.join(DATA, "tpu"), 1)
    return R(tr, W(units=2), S(compiled=object()))


def test_seconds_by_scope_covers_the_window(run):
    by = scopes.seconds_by_scope(run)
    assert None not in by                     # every op is in the map
    lo, hi = run.trace.window
    total = sum(min(o.end, hi) - max(o.start, lo)
                for o in run.trace.devices[0]
                if min(o.end, hi) > max(o.start, lo)) / 1e9
    assert sum(by.values()) == pytest.approx(total)
    kernel = run.trace.op_seconds(
        lambda op: scopes.instruction(op.name) in
        ("rowsplit_execute.1", "copy.1", "copy.2", "copy-start.1",
         "copy-done.1"))
    assert by["kernel"] == pytest.approx(kernel)


def test_ms_per_call_sums_named_scopes(run):
    by = scopes.seconds_by_scope(run)
    want = 1e3 * (by["gather_vals"] + by["widen_b"]) / 2
    assert scopes.ms_per_call(run, scopes.WRAPPER) == pytest.approx(want)
    assert scopes.ms_per_call(run, scopes.DENSE) == 0.0
    run.window.units = 0
    assert scopes.ms_per_call(run, scopes.WRAPPER) is None


def test_unmapped_ops_and_older_programs(run, monkeypatch):
    import repro.obs
    monkeypatch.setattr(repro.obs, "device_scopes", lambda c: {})
    assert set(scopes.seconds_by_scope(run)) == {None}
    monkeypatch.delattr(repro.obs, "device_scopes")   # a program without
    assert scopes.seconds_by_scope(run) is None       # the join
    assert scopes.ms_per_call(run, scopes.WRAPPER) is None


def test_no_trace_or_no_program_reads_nothing(run):
    assert scopes.seconds_by_scope(dataclasses.replace(run, trace=None)) \
        is None
    assert scopes.seconds_by_scope(
        dataclasses.replace(run, system=object())) is None


def test_instruction_name():
    assert scopes.instruction("%fusion.12 = f32[8] fusion(%a)") \
        == "fusion.12"
    assert scopes.instruction("%copy-start = (f32[8]) copy-start()") \
        == "copy-start"


def test_plan_host_reads_fingerprint_and_row_ids():
    from bench.harness import metric_reader
    from conftest import REPO
    from repro import obs
    read = metric_reader(REPO, "plan_host_s")
    fam = obs.registry.histogram("plan_build_step_seconds",
                                 labels=("method", "step"))
    before = read(None)
    fam.labels(method="t-bench", step="fingerprint").observe(0.25)
    fam.labels(method="t-bench", step="row_ids").observe(0.5)
    fam.labels(method="t-bench", step="structure").observe(4.0)
    assert read(None) == pytest.approx(before + 0.75)
