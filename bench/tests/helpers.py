"""Tiny copies of the benchmark's cells, and runs of them on the CPU.

``make_tiny_root`` builds a checkout-shaped tree: ``BENCHMARK.json`` and
a copy of ``bench/`` (its tests left out) in which every configuration
file carries the sizes of ``bench/tests/tiny/<config>.json``: a few
thousand parameters or nodes.  A configuration with no such file is an
error, never a run at its published widths.  The harness runs there with
its look for a chip skipped; Pallas kernels run in interpret mode.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import subprocess
import sys

from bench import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_DIR = os.path.join("bench", "tests", "tiny")
FORCE_DEVICES = "--xla_force_host_platform_device_count"


def make_tiny_root(path: str, src: str = REPO) -> str:
    """The tree of checkout ``src`` at tiny sizes, under ``path``."""
    spec = harness.load_json(os.path.join(src, "BENCHMARK.json"))
    tiny = {}
    for c in spec["configs"]:
        rel = os.path.join(TINY_DIR, c["name"] + ".json")
        if not os.path.exists(os.path.join(src, rel)):
            raise FileNotFoundError(
                f"configuration {c['name']!r} has no tiny sizes for the CPU "
                f"tests: add {rel}, a JSON object of the keys of "
                f"{c['file']} to replace")
        tiny[c["file"]] = harness.load_json(os.path.join(src, rel))
    shutil.copytree(os.path.join(src, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(src, "BENCHMARK.json"), path)
    for rel, sizes in tiny.items():
        f = os.path.join(path, rel)
        cfg = harness.load_json(f)
        cfg.update(sizes)
        with open(f, "w") as fh:
            json.dump(cfg, fh)
    return path


def _args(cell, seed, seconds, trace):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]


def _result(stdout: str):
    lines = stdout.strip().splitlines()
    return json.loads(lines[-1]) if lines else None


def run_cell(root, cell, *, seed=2147483653, seconds=1.0, trace=0):
    """One harness run of ``cell`` under ``root`` in this process, on the
    devices it has; returns (rc, result)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(_args(cell, seed, seconds, trace), root=root,
                          require_tpu=False)
    return rc, _result(out.getvalue())


def run_cell_on_devices(root, cell, devices, *, seed=2147483653,
                        seconds=1.0, trace=0, timeout=300):
    """One harness run of ``cell`` under ``root`` in a child process whose
    CPU backend has ``devices`` devices: the count is fixed when a backend
    starts, and this process's has started with its own.  ``bench`` comes
    from ``root``, the program from this checkout.  Returns (rc, result,
    stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(FORCE_DEVICES)]
    env["XLA_FLAGS"] = " ".join(flags + [f"{FORCE_DEVICES}={devices}"])
    code = (f"import sys; sys.path.insert(0, {root!r}); "
            "from bench import harness; "
            f"sys.exit(harness.main({_args(cell, seed, seconds, trace)!r}, "
            f"root={root!r}, require_tpu=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    return p.returncode, _result(p.stdout), p.stderr
