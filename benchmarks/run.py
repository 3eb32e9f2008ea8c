"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  See benchmarks/common.py for the
timing methodology note (XLA impls timed on CPU; Pallas bodies validated in
interpret mode by tests/).

Every ``bench_*.py`` module in this directory must appear in ``MODS`` —
``check_registration()`` asserts it at startup (and in tests), so a new
bench can't be silently left out of CI.
"""
from __future__ import annotations

import os
import sys


def _mods():
    from . import (bench_batched, bench_corpus, bench_epilogue,
                   bench_fig1_imbalance, bench_fig4_aspect,
                   bench_fig5_rows, bench_fig6_heuristic,
                   bench_fig7_density, bench_plan_reuse,
                   bench_serving, bench_sharded, bench_table1_analysis,
                   bench_train_step, bench_moe_balance)
    return [
        ("fig1", bench_fig1_imbalance),
        ("fig4", bench_fig4_aspect),
        ("fig5", bench_fig5_rows),
        ("fig6", bench_fig6_heuristic),
        ("fig7", bench_fig7_density),
        ("table1", bench_table1_analysis),
        ("moe", bench_moe_balance),
        ("plan", bench_plan_reuse),
        ("batched", bench_batched),
        ("epilogue", bench_epilogue),
        ("sharded", bench_sharded),
        ("train", bench_train_step),
        ("corpus", bench_corpus),
        ("serving", bench_serving),
    ]


def check_registration(mods=None) -> list:
    """Every bench_*.py present on disk must be registered. Returns the
    sorted list of unregistered module stems (empty = in sync); ``main``
    refuses to run when it's non-empty."""
    mods = _mods() if mods is None else mods
    here = os.path.dirname(os.path.abspath(__file__))
    on_disk = {f[:-3] for f in os.listdir(here)
               if f.startswith("bench_") and f.endswith(".py")}
    registered = {mod.__name__.rsplit(".", 1)[-1] for _, mod in mods}
    return sorted(on_disk - registered)


def main() -> None:
    mods = _mods()
    missing = check_registration(mods)
    if missing:
        raise SystemExit(
            f"benchmarks/run.py: unregistered bench modules {missing} — "
            "add them to _mods() so they run in CI")
    only = sys.argv[1] if len(sys.argv) > 1 else None
    printed_header = False
    for name, mod in mods:
        if only and only != name:
            continue
        print(f"# --- {name}: {mod.__doc__.splitlines()[0]}", flush=True)

        def csv(line):
            nonlocal printed_header
            if line.startswith("name,") and printed_header:
                return
            if line.startswith("name,"):
                printed_header = True
            print(line, flush=True)

        mod.run(csv=csv)


if __name__ == "__main__":
    main()
