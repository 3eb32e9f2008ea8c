"""The chip benchmark: one harness, driven by the files named in
``BENCHMARK.json`` (see ``bench/harness.py``)."""
