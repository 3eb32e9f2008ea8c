"""A cell, a configuration (on one chip or four), a system, a traffic mix
and a metric are added by adding their files and ``BENCHMARK.json``
entries alone: no file the benchmark already has changes."""
import json
import os
import shutil

import pytest

from bench.tests.helpers import (REPO, make_tiny_root, run_cell,
                                 run_cell_on_devices)

CALLS_DONE = '''"""Test metric: calls completed in the window."""


def read(run):
    return float(run.window.units)
'''

DEVICES_HELD = '''"""Test metric: the devices the harness handed the system."""


def read(run):
    return float(len(run.system.devices))
'''

# A system of a new family that shards over every device it is given:
# the rows of A over a one-axis mesh, x replicated, A @ x.
ROW_SHARD = '''"""Test system: A @ x with A's rows sharded over the cell's chips."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench import traffic as T
from bench.references import row_shard as ref


class System:
    CONTROL = None

    def __init__(self, cfg, traffic, seed, clock, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices = devices

    def setup(self):
        mesh = Mesh(np.array(self.devices), ("rows",))
        r = T.rng(self.seed, 0)
        shape = (self.cfg["rows"], self.cfg["cols"])
        self.a = r.standard_normal(shape, dtype=np.float32)
        self.x = [r.standard_normal((self.cfg["cols"],
                                     self.traffic["features"]),
                                    dtype=np.float32)
                  for _ in range(self.traffic["pool"])]
        self.a_dev = jax.device_put(
            self.a, NamedSharding(mesh, PartitionSpec("rows", None)))
        self.x_dev = [jax.device_put(x, NamedSharding(mesh, PartitionSpec()))
                      for x in self.x]
        matmul = jax.jit(lambda a, x: jnp.matmul(a, x, precision="highest"))
        self.compiled = matmul.lower(self.a_dev, self.x_dev[0]).compile()
        jax.block_until_ready(self.call(0))

    def call(self, i):
        return self.compiled(self.a_dev, self.x_dev[i % len(self.x_dev)])

    def unit_tokens(self, i):
        return None

    def unit_work(self, i):
        rows, cols = self.a.shape
        return {"flops": 2.0 * rows * cols * self.traffic["features"],
                "spmm": []}

    def release(self):
        del self.compiled, self.a_dev, self.x_dev

    def check(self, samples, quant=None):
        worst, shards = 0.0, len(self.devices)
        for i, got in samples:
            shards = min(shards, len(got.sharding.device_set))
            want = ref.matmul(self.a, self.x[i % len(self.x)])
            worst = max(worst, float(np.max(np.abs(np.asarray(got) - want)
                                            / (np.abs(want) + 1.0))))
        return {"max_err": worst,
                "chips_unused": float(len(self.devices) - shards)}
'''

ROW_SHARD_REF = '''"""Test reference: A @ x in float64 with numpy."""
import numpy as np


def matmul(a, x):
    return a.astype(np.float64) @ x.astype(np.float64)
'''


def _files(top):
    for d, _, fs in os.walk(top):
        for f in fs:
            if "__pycache__" not in d:
                yield os.path.join(d, f)


def _checkout(path):
    """A copy of this checkout's benchmark: ``BENCHMARK.json`` and
    ``bench/``, with the bytes of every file under ``bench/``."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    return path, {p: open(p, "rb").read()
                  for p in _files(os.path.join(path, "bench"))}


def _add(root, files: dict, config: dict, cell: dict, metrics: list,
         join=()):
    """New files, and entries appended to ``BENCHMARK.json``: the
    configuration, the cell, new end-to-end metrics of that cell, and the
    cell appended to the ``workloads`` of the existing end-to-end metrics
    named in ``join``."""
    for rel, data in files.items():
        p = os.path.join(root, rel)
        assert not os.path.exists(p), rel
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(data))
    with open(f"{root}/BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append(config)
    spec["workloads"].append(cell)
    for m in spec["end_to_end"]:
        if m["name"] in join:
            m["workloads"].append(cell["name"])
    spec["end_to_end"] += metrics
    with open(f"{root}/BENCHMARK.json", "w") as f:
        json.dump(spec, f)


def _unchanged(before):
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def _e2e(name, unit, cell):
    return {"name": name, "unit": unit, "better": "higher", "bound": 0.25,
            "source": "host_clock", "workloads": [cell]}


def test_new_cell_config_traffic_and_metric_by_files_alone(tmp_path):
    """One chip: a configuration of an existing family at published-style
    widths, cut for the CPU by its own tiny file."""
    src, before = _checkout(str(tmp_path / "checkout"))
    with open(f"{src}/bench/configs/granite-3-2b.json") as f:
        cfg = json.load(f)
    cfg.update(name="test-lm", hidden_size=4096, intermediate_size=12800,
               num_hidden_layers=40, num_attention_heads=32,
               num_key_value_heads=8)
    tiny = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=1,
                num_attention_heads=2, num_key_value_heads=1, vocab_size=256)
    cell = "test-lm.score-64x2"
    _add(src, {
        "bench/configs/test-lm.json": cfg,
        "bench/tests/tiny/test-lm.json": tiny,
        "bench/traffic/score-64x2.json": {
            "loop": "closed_batch", "batch": 2, "length": 64, "pool": 4,
            "sample": 2},
        f"bench/limits/{cell}.json": {"logits_rms": {"limit": 0.05}},
        "bench/metrics/calls_done.py": CALLS_DONE,
        "bench/metrics/devices_held.py": DEVICES_HELD,
    }, {"name": "test-lm", "source": "test",
        "file": "bench/configs/test-lm.json", "reduced": [], "why": "test"},
        {"name": cell, "config": "test-lm", "traffic": "score-64x2",
         "chips": 1, "why": "test"},
        [_e2e("calls_done", "calls", cell),
         _e2e("devices_held", "devices", cell)], join=("tokens_per_s",))
    _unchanged(before)
    root = make_tiny_root(str(tmp_path / "tiny"), src=src)
    with open(f"{root}/bench/configs/test-lm.json") as f:
        assert json.load(f)["hidden_size"] == 32
    rc, res = run_cell(root, cell)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["metrics"]["calls_done"]["value"] >= 1
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["metrics"]["devices_held"]["value"] == 1
    assert res["device"]["count"] == 1
    assert "logits_rms" in res["checks"]


def test_new_four_chip_config_and_system_by_files_alone(tmp_path):
    """Four chips: a configuration of a new family whose system builds
    its mesh from the devices the harness hands it, run on four CPU
    devices."""
    src, before = _checkout(str(tmp_path / "checkout"))
    cell = "test-rows.rows-f8"
    _add(src, {
        "bench/systems/row_shard.py": ROW_SHARD,
        "bench/references/row_shard.py": ROW_SHARD_REF,
        "bench/configs/test-rows.json": {"name": "test-rows",
                                         "family": "row_shard",
                                         "rows": 65536, "cols": 8192},
        "bench/tests/tiny/test-rows.json": {"rows": 64, "cols": 32},
        "bench/traffic/rows-f8.json": {"loop": "closed_batch",
                                       "features": 8, "pool": 2,
                                       "sample": 2},
        f"bench/limits/{cell}.json": {"max_err": {"limit": 1e-5},
                                      "chips_unused": {"limit": 0}},
        "bench/metrics/calls_done.py": CALLS_DONE,
        "bench/metrics/devices_held.py": DEVICES_HELD,
    }, {"name": "test-rows", "source": "test",
        "file": "bench/configs/test-rows.json", "reduced": [],
        "why": "test"},
        {"name": cell, "config": "test-rows", "traffic": "rows-f8",
         "chips": 4, "why": "test"},
        [_e2e("calls_done", "calls", cell),
         _e2e("devices_held", "devices", cell)])
    _unchanged(before)
    root = make_tiny_root(str(tmp_path / "tiny"), src=src)
    rc, res, err = run_cell_on_devices(root, cell, 4)
    assert rc == 0 and res is not None, err[-4000:]
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["devices_held"]["value"] == 4
    assert res["checks"]["chips_unused"]["value"] == 0
    assert res["device"]["count"] == 4
    # On fewer devices than the cell asks for, no result.
    rc, res, err = run_cell_on_devices(root, cell, 2)
    assert rc != 0 and res is None
    assert "asks for 4 chips" in err


def test_config_without_tiny_file_fails_at_once(tmp_path):
    src, _ = _checkout(str(tmp_path / "checkout"))
    _add(src, {"bench/configs/untested.json": {"name": "untested"}},
         {"name": "untested", "source": "test",
          "file": "bench/configs/untested.json", "reduced": [],
          "why": "test"},
         {"name": "untested.score-128", "config": "untested",
          "traffic": "score-128", "chips": 1, "why": "test"}, [])
    with pytest.raises(FileNotFoundError,
                       match="add bench/tests/tiny/untested.json"):
        make_tiny_root(str(tmp_path / "tiny"), src=src)


def test_missing_metric_file_is_an_error(tmp_path):
    root = make_tiny_root(str(tmp_path))
    os.remove(f"{root}/bench/metrics/setup_s.py")
    try:
        run_cell(root, "granite-3-2b.score-128")
    except FileNotFoundError as e:
        assert "setup_s" in str(e)
    else:
        raise AssertionError("a metric with no reader ran")


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
