"""Full-graph feature aggregation ``C = A @ X`` through the program's
``SparseMatrix``.

Set-up makes the graph as the Graph 500 specification's Kronecker
generator does: ``edgefactor * 2**SCALE`` edges, each placing its end
points bit by bit in the quadrants of the adjacency matrix with
probabilities ``A``, ``B``, ``C`` and ``1 - A - B - C``; then the vertex
labels are permuted at random and each edge gets a weight uniform in
``[0, 1)``, as the specification's SSSP kernel draws them.  The edge list
and the relabelling come from a fixed stream (``edge_list_seed``), so
every seed has the same pattern, the same plan shapes and the same work;
``--seed`` draws the weights and the features.  The graph is undirected: each edge is a
nonzero in both directions, self-loops are dropped and repeated edges
keep their lightest weight.  The program builds its plan
(``SparseMatrix.from_csr`` with the configuration's ``method``),
compiles ``A @ X`` at the traffic's feature width and warms it.  The
window multiplies a small pool of seeded feature matrices in turn.

The check compares every sampled output with the plain segment-sum
reference (``bench/references/graph_agg.py``): the largest error of any
output entry as a share of ``|A| @ |X|`` at that entry.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as T
from bench import work
from bench.references import graph_agg as ref


def kronecker_edges(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """The specification's Kronecker edge list, relabelled: ``(start,
    end)`` vertex ids of ``edgefactor * 2**SCALE`` edges."""
    scale = int(cfg["SCALE"])
    m = int(cfg["edgefactor"]) << scale
    a, b, c = cfg["A"], cfg["B"], cfg["C"]
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    r = T.rng(int(cfg["edge_list_seed"]), 3)
    start = np.zeros(m, np.int64)
    end = np.zeros(m, np.int64)
    for bit in range(scale):
        start_bit = r.random(m) > ab
        end_bit = r.random(m) > np.where(start_bit, c_norm, a_norm)
        start |= start_bit.astype(np.int64) << bit
        end |= end_bit.astype(np.int64) << bit
    label = r.permutation(1 << scale)
    return label[start], label[end]


def make_graph(cfg: dict, seed: int):
    """Host-side CSR arrays of the seeded graph: rows and columns are
    vertices, one nonzero per direction of each edge."""
    n = 1 << int(cfg["SCALE"])
    start, end = kronecker_edges(cfg)
    weight = T.rng(seed, 3).random(start.size, dtype=np.float32)
    keep = start != end
    rows = np.concatenate([start[keep], end[keep]])
    cols = np.concatenate([end[keep], start[keep]])
    vals = np.concatenate([weight[keep], weight[keep]])
    key = rows * n + cols
    order = np.lexsort((vals, key))              # lightest first per pair
    key = key[order]
    first = np.ones(key.size, bool)
    first[1:] = key[1:] != key[:-1]
    order = order[first]
    rows, cols, vals = rows[order], cols[order], vals[order]
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=row_ptr[1:])
    return (row_ptr.astype(np.int32), cols.astype(np.int32),
            vals.astype(np.float32))


@functools.partial(jax.jit, static_argnames=("shape",))
def _features(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


class System:
    """One graph under one aggregation traffic."""

    # The control: the reference one precision step below float32 at
    # full precision (three bfloat16 passes).
    CONTROL = "high"

    def __init__(self, cfg: dict, traffic: dict, seed: int, clock):
        self.cfg, self.traffic, self.seed, self.clock = cfg, traffic, seed, \
            clock
        self.n = 1 << int(cfg["SCALE"])
        self.f = int(traffic["features"])

    def setup(self) -> None:
        from repro.core import CSR, PlanPolicy, SparseMatrix
        if self.traffic["loop"] != "closed_batch":
            raise ValueError(f"graph_agg has no loop "
                             f"{self.traffic['loop']!r}")
        with self.clock.phase("generate"):
            rp, ci, va = make_graph(self.cfg, self.seed)
            self.row_ptr, self.col_ind, self.vals = (
                jnp.asarray(rp), jnp.asarray(ci), jnp.asarray(va))
            key = jax.random.PRNGKey(T.jax_seed(self.seed, 4))
            self.x = [_features(k, (self.n, self.f)) for k in
                      jax.random.split(key, int(self.traffic["pool"]))]
            jax.block_until_ready(self.x)
        with self.clock.phase("plan_build"):
            csr = CSR(self.row_ptr, self.col_ind, self.vals, (self.n, self.n))
            self.a = SparseMatrix.from_csr(
                csr, PlanPolicy(method=self.cfg["method"],
                                with_transpose=False))
            jax.block_until_ready(self.a)
        with self.clock.phase("compile"):
            self.compiled = jax.jit(lambda a, x: a @ x).lower(
                self.a, self.x[0]).compile()
        with self.clock.phase("warmup"):
            jax.block_until_ready(self.call(0))

    # ------------------------------------------------------- timed path ---

    def call(self, i: int):
        return self.compiled(self.a, self.x[i % len(self.x)])

    def unit_tokens(self, i: int):
        return None

    def spmm_calls(self) -> list:
        nnz = int(self.col_ind.shape[0])
        return [work.SpmmCall(m=self.n, k=self.n, nnz=nnz, n=self.f,
                              val_bytes=4, b_bytes=4, c_bytes=4)]

    def unit_work(self, i: int) -> dict:
        calls = self.spmm_calls()
        return {"flops": sum(c.flops for c in calls), "spmm": calls}

    # ------------------------------------------------------------ check ---

    def release(self) -> None:
        from repro import engine
        for name in ("compiled", "a"):
            if hasattr(self, name):
                delattr(self, name)
        engine.clear_cache()
        gc.collect()

    def check(self, samples, quant=None) -> dict:
        """``samples`` are ``(i, C)`` pairs the timed path produced.  With
        ``quant`` the reference at that lower precision stands in for the
        program (the control)."""
        worst = 0.0
        for i, got in samples:
            x = self.x[i % len(self.x)]
            want, scale = ref.aggregate(self.row_ptr, self.col_ind,
                                        self.vals, x)
            if quant is not None:
                got, _ = ref.aggregate(self.row_ptr, self.col_ind,
                                       self.vals, x, quant)
            diff = jnp.abs(jnp.asarray(got, jnp.float32) - want)
            rel = jnp.where(scale > 0, diff / jnp.where(scale > 0, scale, 1),
                            diff)
            worst = max(worst, float(jnp.max(rel)))
            del want, scale, diff, rel
        return {"agg_err": worst}
