#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload granite-3-2b.score-128 --seed 7 \
        --seconds 10 --trace 0

The cells, their configurations and metrics are listed in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` finds
each one's files by name.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``checks``).  Exits non-zero, printing no such line, when JAX finds no TPU
or fewer chips than the cell asks for.  ``setup_s`` counts from the moment
the chips are found; the time before that is reported beside it.
"""
import os
import sys
import time

# The interpreter has just started.
T_PROCESS = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_process=T_PROCESS))
