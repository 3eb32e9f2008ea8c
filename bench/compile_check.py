#!/usr/bin/env python3
"""Compile each cell's SpMM kernels at their real shapes for a described
(not attached) TPU v5e, with no chip:

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/compile_check.py

Shapes only: the CSR and plan structures are ``jax.eval_shape`` of the
program's own structure builders, so nothing of real size runs here.
Prints, per kernel, the method, grid-defining sizes, compile seconds, the
compiled program's memory analysis and its ``tpu_custom_call`` count.
What this cannot say: results, times, or whether the program fits beside
the rest of a run's state.
"""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench.harness import load_json  # noqa: E402


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _csr(m, k, nnz):
    from repro.core.csr import CSR
    return CSR(jax.ShapeDtypeStruct((m + 1,), jnp.int32),
               jax.ShapeDtypeStruct((nnz,), jnp.int32),
               jax.ShapeDtypeStruct((nnz,), jnp.float32), (m, k))


def compile_one(label, fn, args, chip):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*_on(chip, args)).compile()
    dt = time.perf_counter() - t0
    n = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    print(f"{label}: compiled in {dt:.2f} s; {n} tpu_custom_call; "
          f"{compiled.memory_analysis()}", flush=True)
    return n


def main() -> int:
    from jax.experimental import topologies

    from repro.kernels import merge_spmm, ops, rowsplit_spmm
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    g = load_json(os.path.join(ROOT, "bench", "configs",
                               "granite-3-2b.json"))
    d, ff, keep = g["hidden_size"], g["intermediate_size"], g["keep_per_row"]
    for name, m, k in (("w1/w3", ff, d), ("w2", d, ff)):
        kr = int(round(keep * k))
        a = _csr(m, k, m * kr)
        st = jax.eval_shape(lambda a: rowsplit_spmm.plan_rowsplit_structure(
            a, l_pad=kr), a)
        b = jax.ShapeDtypeStruct((1, k, 128), jnp.float32)
        compile_one(f"granite-3-2b {name} rowsplit ({m} x {k}, {kr} a row, "
                    f"ELL {st['cols'].shape})",
                    lambda s, v, b, m=m: ops.rowsplit_execute(
                        s, v, b, m=m, interpret=False),
                    (st, a.vals, b), chip)

    from bench.systems.graph_agg import make_graph
    c = load_json(os.path.join(ROOT, "bench", "configs",
                               "graph500-s16.json"))
    row_ptr, _, _ = make_graph(c, 0)
    n, nnz = row_ptr.size - 1, int(row_ptr[-1])
    a = _csr(n, n, nnz)
    t = merge_spmm.default_t(n, nnz)
    st = jax.eval_shape(lambda a: merge_spmm.plan_merge_structure(a, t=t), a)
    x = jax.ShapeDtypeStruct((n, 128), jnp.float32)
    compile_one(f"graph500-s16 merge ({nnz} nonzeros, T={t}, "
                f"{st['cols'].shape[0]} chunk slots, "
                f"{-(-n // merge_spmm.DEFAULT_TK_MAX)} K-tiles)",
                lambda s, v, x: ops.merge_execute(s, v, x, m=n,
                                                  interpret=False),
                (st, a.vals, x), chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
