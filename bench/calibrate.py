#!/usr/bin/env python3
"""Readings that the limits deciding ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload graph500-s16.agg-f128 \
        --seeds 12 --control-seeds 3 --seconds 8 --out calib.json

In one process, for each of ``--seeds`` seeds: set the cell up at its own
size, drive its timed path for a short window under its own traffic,
sample the answers as a run does and compare them with the reference:
the program's readings.  For the first ``--control-seeds`` of them, the
reference computed at the precision below the configuration's stands in
for the program (``System.CONTROL``): the control's readings, which have
to fail.  Writes every reading and, per number, the largest program
reading (``lower``) and the smallest control reading (``upper``).
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(ROOT, args.workload)
    harness.prepare_jax(ROOT)
    devs = harness.chips(cell.chips, require_tpu=True)
    cls = harness.system_class(cell)
    loop = harness.LOOPS[cell.traffic["loop"]]
    rows = []
    for j in range(args.seeds):
        seed = args.first_seed + 7919 * j
        t0 = time.perf_counter()
        system = cls(cell.config, cell.traffic, seed, harness.SetupClock(),
                     devs)
        system.setup()
        sampler = harness.Sampler(int(cell.traffic["sample"]), seed)
        window = loop(system, args.seconds, sampler)
        samples = sampler.sample()
        system.release()
        row = {"seed": seed, "units": window.units,
               "program": system.check(samples)}
        if j < args.control_seeds:
            row["control"] = system.check(samples, quant=cls.CONTROL)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
        del system, samples, sampler
        gc.collect()
    names = sorted(rows[0]["program"])
    summary = {k: {"lower": max(r["program"][k] for r in rows),
                   "upper": min(r["control"][k] for r in rows
                                if "control" in r)}
               for k in names}
    out = {"workload": args.workload, "device": devs[0].device_kind,
           "rows": rows, "summary": summary}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
