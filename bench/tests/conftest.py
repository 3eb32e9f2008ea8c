"""Tiny copies of the benchmark's cells for CPU tests.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

``tiny_root`` builds, in a temporary directory, a checkout-shaped tree:
``BENCHMARK.json`` and a copy of ``bench/`` whose configuration files are
cut to a few thousand parameters or nodes.  The harness runs there with
its look for a chip skipped; Pallas kernels run in interpret mode.
"""
import json
import os
import shutil
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = {
    "granite-3-2b": dict(hidden_size=64, intermediate_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2, vocab_size=512),
    "graph500-s16": dict(SCALE=8),
}


def make_tiny_root(path: str) -> str:
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    for name, sizes in TINY.items():
        f = os.path.join(path, "bench", "configs", name + ".json")
        with open(f) as fh:
            cfg = json.load(fh)
        cfg.update(sizes)
        with open(f, "w") as fh:
            json.dump(cfg, fh)
    return path


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))


def run_cell(root, cell, *, seed=2147483653, seconds=1.0, trace=0):
    """One harness run of ``cell`` under ``root``; returns (rc, result)."""
    import contextlib
    import io

    from bench import harness
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root,
                          require_tpu=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
