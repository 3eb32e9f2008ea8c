"""Test substrate: forced multi-device CPU runs for sharded tests.

A real TPU pod isn't available on a dev box or in CI, but XLA can split
the host CPU into any number of devices — *if* the flag lands before jax
initializes.  Two pieces make sharded tests runnable (not skipped)
everywhere:

* ``REPRO_FORCE_DEVICES=N``: honored by the repository's root
  ``conftest.py`` (four devices without it), at import time — before any
  test module imports jax — through
  ``--xla_force_host_platform_device_count=N`` in ``XLA_FLAGS``.  This
  is what ``make test-sharded`` sets.
* :func:`run_pytest_forced_devices`: runs a pytest target in a *fresh
  subprocess* with the env var set.  ``tests/test_distributed_spmm.py``
  uses it to wrap its device-hungry tests when the current process came
  up with too few devices (the usual four-device ``pytest -q``), so the
  tier-1 suite exercises the full 8-device matrix on any box.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest


def run_pytest_forced_devices(target: str, n_devices: int,
                              timeout: int = 1500):
    """Run ``pytest <target>`` in a subprocess with N forced CPU devices.

    Returns the completed process (stdout/stderr captured, text mode).
    The child inherits the parent's interpreter and gets ``src`` on its
    PYTHONPATH, ``REPRO_FORCE_DEVICES`` (picked up by the root conftest
    before jax initializes there), and a marker env var tests can use to
    avoid re-spawning recursively.
    """
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["REPRO_FORCE_DEVICES"] = str(n_devices)
    env["_REPRO_FORCED_CHILD"] = "1"
    # Drop any existing device-count force so the child's conftest can
    # apply N; every other XLA flag passes through.
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    else:
        env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "-p", "no:cacheprovider",
         "-W", "error::DeprecationWarning", target],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="session")
def forced_device_run():
    """Fixture handle on :func:`run_pytest_forced_devices`."""
    return run_pytest_forced_devices
