"""A tiny-size run of each cell, on the CPU with interpret-mode kernels:
the whole run (set-up, window, check, result line) with the look for a
chip skipped.  Says nothing about speed."""
import pytest

from bench.harness import load_json
from bench.tests.helpers import REPO, run_cell

CELLS = [w["name"] for w in
         load_json(f"{REPO}/BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(tiny_root, cell):
    rc, res = run_cell(tiny_root, cell)
    assert rc == 0
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    spec = load_json(f"{REPO}/BENCHMARK.json")
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("cell", CELLS[:1])
def test_traced_run_reports_per_layer_metrics(tiny_root, cell):
    rc, res = run_cell(tiny_root, cell, trace=1)
    assert rc == 0 and res["correct"] is True
    assert "window_s" in res["device"] and "busy_s" in res["device"]
    # On the CPU there is no device plane: the device readers return
    # nothing, and the host-clock ones still report.
    assert "plan_build_s" in res["metrics"]
    assert "spmm_roofline.model" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
