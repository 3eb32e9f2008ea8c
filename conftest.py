"""Every pytest process of this repository gets four CPU devices, as
many as the largest benchmark cell asks for, so that a four-chip cell's
tiny run (``bench/tests``) runs in the test's own process like the
others.  ``REPRO_FORCE_DEVICES=N`` asks for N instead (``make
test-sharded``, and the children of ``tests/conftest.
run_pytest_forced_devices``).  The count is fixed when JAX's backend
starts, so it is set here, before any test module imports JAX; a count
already in ``XLA_FLAGS`` stands.
"""
import os

_FLAG = "--xla_force_host_platform_device_count"
_flags = os.environ.get("XLA_FLAGS", "")
if _FLAG not in _flags:
    _n = int(os.environ.get("REPRO_FORCE_DEVICES") or 4)
    os.environ["XLA_FLAGS"] = f"{_flags} {_FLAG}={_n}".strip()
