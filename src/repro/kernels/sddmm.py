"""Sampled dense-dense matmul (SDDMM) — Pallas TPU kernel.

The backward pass of ``C = A @ B`` with respect to the CSR values is a
dense-dense product *sampled at the sparsity pattern*:

    dvals[p] = dC[row[p], :] · B[col[p], :]       for each nonzero p.

This is the gather-dot dual of the forward SpMM: instead of gathering B
rows by column index and scattering into C, we gather a dC row and a B row
per nonzero and reduce across the lane axis.  The nonzero stream is chunked
``TQ`` at a time (the same equal-nonzero balancing as the merge kernel —
cost is O(nnz), independent of row distribution, so the backward pass
inherits the paper's load-balance guarantees), and the reduction over the
dense axis n runs as an inner grid dimension with a VMEM accumulator.

Batched execution adds a leading ``batch`` grid axis: ``dc (batch, m, n)``
and ``b (batch, k, n)`` yield per-element dots ``(batch, P, TQ)`` in one
dispatch.  The caller reduces over the batch when the values are shared
across it (``repro.core.spmm``'s batched VJP) — keeping the axis here is
what makes the same kernel serve ``jax.vmap``'s per-element semantics.

Padded nonzeroes must arrive with in-bounds (row, col) = (0, 0); the caller
masks their outputs (``repro.kernels.ops.sddmm``).
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs import scope

TN = 128   # lanes of the dense axis per grid step
TQ = 128   # nonzeroes per chunk


def _sddmm_kernel(rows_ref, cols_ref, dc_ref, b_ref, o_ref, acc_ref, *,
                  n_j: int, acc_dtype):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = rows_ref[0]                                    # (TQ,)
    cols = cols_ref[0]                                    # (TQ,)
    # Row-major coalesced gathers of dC and B rows (lane-contiguous slices).
    dcg = jnp.take(dc_ref[0], rows, axis=0).astype(acc_dtype)     # (TQ, TN)
    bg = jnp.take(b_ref[0], cols, axis=0).astype(acc_dtype)       # (TQ, TN)
    acc_ref[...] += jnp.sum(dcg * bg, axis=1)[None, :]

    @pl.when(j == n_j - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def sddmm_pallas(rows: jax.Array, cols: jax.Array, dc: jax.Array,
                 b: jax.Array, *, tn: int = TN,
                 interpret: bool = False) -> jax.Array:
    """``rows``/``cols`` are (P, TQ) chunked nonzero coordinates; ``dc`` is
    (batch, m, n), ``b`` is (batch, k, n), n % tn == 0.  Returns
    (batch, P, TQ) float32 dots — per batch element; callers with values
    shared across the batch reduce over axis 0 themselves."""
    p, tq = rows.shape
    batch, m, n = dc.shape
    _, k, _ = b.shape
    acc_dtype = jnp.float32
    grid = (batch, p, n // tn)
    kernel = functools.partial(_sddmm_kernel, n_j=n // tn,
                               acc_dtype=acc_dtype)
    with scope("kernel"):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tq), lambda bb, i, j: (i, 0)),
                pl.BlockSpec((1, tq), lambda bb, i, j: (i, 0)),
                pl.BlockSpec((1, m, tn), lambda bb, i, j: (bb, 0, j)),
                pl.BlockSpec((1, k, tn), lambda bb, i, j: (bb, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, 1, tq), lambda bb, i, j: (bb, i, 0)),
            out_shape=jax.ShapeDtypeStruct((batch, p, tq), acc_dtype),
            scratch_shapes=[pltpu.VMEM((1, tq), acc_dtype)],
            interpret=interpret,
            name="sddmm",
        )(rows, cols, dc, b)


# ----------------------------------------------------- static launch model ---


def launch_models(*, nnz_pad, m, k, n, batch, dc_dtype="float32",
                  b_dtype="float32"):
    """Static model of ``sddmm_pallas``'s one launch, as dispatched by
    ``ops.sddmm``: the nonzero stream chunked ``(P, TQ)``, ``dc``/``b``
    lane-padded to ``TN`` multiples, output ``(batch, P, TQ)`` f32.

    This is the backward (values-cotangent) kernel the forward audits
    never stage; ``repro.analysis.access``/``traffic`` pull it in
    explicitly so the ``custom_vjp`` path gets the same coalescing and
    bytes coverage as the forward launches.
    """
    from .introspect import KernelBlock, KernelLaunch
    p = -(-nnz_pad // TQ)
    n_pad = TN * (-(-n // TN))
    n_j = n_pad // TN
    blocks = [
        KernelBlock("rows", (1, TQ), "int32",
                    lambda bb, i, j: (i, 0), (p, TQ), "in"),
        KernelBlock("cols", (1, TQ), "int32",
                    lambda bb, i, j: (i, 0), (p, TQ), "in"),
        KernelBlock("dc", (1, m, TN), dc_dtype,
                    lambda bb, i, j: (bb, 0, j), (batch, m, n_pad), "in"),
        KernelBlock("b", (1, k, TN), b_dtype,
                    lambda bb, i, j: (bb, 0, j), (batch, k, n_pad), "in"),
    ]
    out = KernelBlock("out", (1, 1, TQ), "float32",
                      lambda bb, i, j: (bb, i, 0), (batch, p, TQ), "out")
    blocks += [out, KernelBlock("acc", (1, TQ), "float32", None, (1, TQ),
                                "scratch")]
    return [KernelLaunch(
        label="sddmm", grid=(batch, p, n_j), blocks=tuple(blocks),
        flush=lambda bb, i, j: j == n_j - 1, out=out)]
