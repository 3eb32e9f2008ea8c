"""The benchmark's CPU tests, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They also run with the repository's own tests, in one pytest session and
under several workers: ``bench/tests`` is a package (``bench.tests``), so
no module name clashes with ``tests/``, and the helpers are imported from
``bench.tests.helpers``, never from a ``conftest``.

Each configuration in ``BENCHMARK.json`` brings its tiny sizes in
``bench/tests/tiny/<config>.json``; ``tiny_root`` builds, in a temporary
directory, a checkout-shaped tree at those sizes, and fails at once,
naming the file to add, where one is missing.  Cells on one chip run in
the test's own process.  A cell on several chips runs in a child process
whose CPU backend is given that many devices
(``helpers.run_cell_on_devices``), since a process's device count is
fixed once its backend has started, and another test module may have
started it.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from bench.tests.helpers import make_tiny_root  # noqa: E402

# What ``harness.prepare_jax`` sets, process-wide, for a run.
_JAX_OPTIONS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(autouse=True)
def _jax_options_restored():
    """A run in this process turns JAX's persistent compilation cache on;
    turn it back as it was, so that the next test in this worker, of
    whichever suite, runs as it would alone."""
    import jax
    before = {k: getattr(jax.config, k) for k in _JAX_OPTIONS}
    yield
    if any(getattr(jax.config, k) != v for k, v in before.items()):
        from jax.experimental.compilation_cache import compilation_cache
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
