"""Without a TPU, or without the program beside it, a run exits non-zero
and prints no result line."""
import os
import shutil
import subprocess
import sys

from bench.tests.helpers import REPO

ARGS = ["--workload", "granite-3-2b.score-128", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_files_alone_exit_nonzero_without_result(tmp_path):
    """The look for a chip skipped, as on a machine with one: the run
    fails for want of the program."""
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = ("import sys; sys.path.insert(0, '.'); from bench import harness;"
            f" sys.exit(harness.main({ARGS!r}, require_tpu=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
