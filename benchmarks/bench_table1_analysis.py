"""Table 1 analogue: static work/traffic analysis per kernel.

The paper's Table 1 counts independent instructions, register usage, and
memory-access overhead per thread for row-split vs. merge-based.  The TPU
analogue: per-grid-step work items, VMEM working set (the register-file
analogue), and HBM traffic overhead vs. the nnz lower bound — derived from
the kernels' BlockSpecs, not timed.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import random_csr
from repro.kernels import merge_spmm as MS
from repro.kernels import rowsplit_spmm as RS


def analyze(m=4096, k=4096, mean_len=16, irregular=True, n=128, dtype_b=4):
    npr = (0, 2 * mean_len) if irregular else mean_len
    a = random_csr(jax.random.PRNGKey(0), m, k, nnz_per_row=npr)
    nnz = int(a.nnz())
    lengths = np.diff(np.asarray(a.row_ptr))
    rows = []

    # row-split: ELL pad to max row length rounded to TL
    tl = RS.DEFAULT_TL
    l_pad = int(tl * (-(-max(int(lengths.max()), 1) // tl)))
    work_rs = m * l_pad                      # padded work items
    vmem_rs = (k * RS.TN + RS.TM * RS.TN) * dtype_b  # B panel + C tile
    a_traffic_rs = work_rs * 8 * (n // RS.TN)  # (col,val) per n-tile
    rows.append(("rowsplit", RS.TM * tl, vmem_rs / 2**20,
                 work_rs / nnz, a_traffic_rs / (nnz * 8)))

    # merge: chunks of T nonzeroes, broken at TM-row tiles
    t = MS.default_t(m, a.nnz_pad)
    plan = MS.plan_merge(a, t=t)
    n_chunks = int(plan["cols"].shape[0])
    work_mg = n_chunks * t
    vmem_mg = (k * MS.TN + MS.TM * MS.TN) * dtype_b
    a_traffic_mg = work_mg * 12 * (n // MS.TN)  # (col,val,lrow)
    rows.append(("merge", t, vmem_mg / 2**20,
                 work_mg / nnz, a_traffic_mg / (nnz * 8)))
    return rows, nnz


def run(csv=print):
    csv("name,us_per_call,derived")
    for irregular in (False, True):
        rows, nnz = analyze(irregular=irregular)
        tag = "irregular" if irregular else "regular"
        for name, items, vmem_mb, work_ratio, traffic_ratio in rows:
            csv(f"table1_{tag}_{name}_items_per_step,0,{items}")
            csv(f"table1_{tag}_{name}_vmem_mb,0,{vmem_mb:.2f}")
            csv(f"table1_{tag}_{name}_padded_work_ratio,0,{work_ratio:.2f}")
            csv(f"table1_{tag}_{name}_A_traffic_ratio,0,{traffic_ratio:.2f}")


if __name__ == "__main__":
    run()
