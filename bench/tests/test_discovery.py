"""A cell, a configuration, a traffic mix and a per-layer metric are added
by adding their files and ``BENCHMARK.json`` entries alone: no file the
harness already has changes."""
import json
import os
import shutil

from conftest import make_tiny_root, run_cell

READER = '''"""Test metric: calls completed in the window."""


def read(run):
    return float(run.window.units)
'''


def test_new_cell_config_traffic_and_metric_by_files_alone(tmp_path):
    root = make_tiny_root(str(tmp_path))
    before = {p: open(p, "rb").read()
              for p in _files(os.path.join(root, "bench"))}
    # A new configuration of an existing family ...
    with open(f"{root}/bench/configs/granite-3-2b.json") as f:
        cfg = json.load(f)
    cfg.update(name="tiny-lm", num_hidden_layers=1, vocab_size=256)
    with open(f"{root}/bench/configs/tiny-lm.json", "w") as f:
        json.dump(cfg, f)
    # ... under a new traffic mix, with its own limits and a new metric.
    with open(f"{root}/bench/traffic/score-64x2.json", "w") as f:
        json.dump({"loop": "closed_batch", "batch": 2, "length": 64,
                   "pool": 4, "sample": 2}, f)
    with open(f"{root}/bench/limits/tiny-lm.score-64x2.json", "w") as f:
        json.dump({"logits_rms": {"limit": 0.05}}, f)
    with open(f"{root}/bench/metrics/calls_done.py", "w") as f:
        f.write(READER)
    with open(f"{root}/BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-lm", "source": "test",
                            "file": "bench/configs/tiny-lm.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-lm.score-64x2",
                              "config": "tiny-lm", "traffic": "score-64x2",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny-lm.score-64x2")
    spec["end_to_end"].append({"name": "calls_done", "unit": "calls",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny-lm.score-64x2"]})
    with open(f"{root}/BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    for p, data in before.items():
        assert open(p, "rb").read() == data
    rc, res = run_cell(root, "tiny-lm.score-64x2")
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["calls_done"]["value"] >= 1
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert "logits_rms" in res["checks"]


def test_missing_metric_file_is_an_error(tmp_path):
    root = make_tiny_root(str(tmp_path))
    os.remove(f"{root}/bench/metrics/setup_s.py")
    try:
        run_cell(root, "granite-3-2b.score-128")
    except FileNotFoundError as e:
        assert "setup_s" in str(e)
    else:
        raise AssertionError("a metric with no reader ran")


def _files(top):
    for d, _, fs in os.walk(top):
        for f in fs:
            if "__pycache__" not in d:
                yield os.path.join(d, f)


def test_unknown_device_kind_has_no_peaks(tmp_path):
    from bench.peaks import peak_for
    p = tmp_path / "peaks.json"
    shutil.copy(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                             "peaks.json"), p)
    assert peak_for("TPU v5 lite", str(p))["hbm_bytes_per_s"] == 819e9
    try:
        peak_for("TPU v9 imaginary", str(p))
    except KeyError as e:
        assert "TPU v9 imaginary" in str(e)
    else:
        raise AssertionError("an unknown device got peaks")
