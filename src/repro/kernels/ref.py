"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the allclose sweeps in tests/ and the
"paper-faithful dataflow in plain XLA" baselines for the benchmarks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.csr import CSR
from repro.core.epilogue import apply_epilogue
from repro.core.partition import chunk_segments, partition_spmm


def spmm_dense_ref(a: CSR, b: jax.Array) -> jax.Array:
    """Densify-and-matmul oracle (small matrices only)."""
    return a.to_dense() @ b


def spmm_gather_ref(a: CSR, b: jax.Array) -> jax.Array:
    """Gather/segment-sum oracle: the CSR dataflow with no blocking at all."""
    _, nnz_rows = partition_spmm(a, t=max(a.nnz_pad, 1))
    prods = a.vals[:, None] * b[a.col_ind]          # (nnz_pad, n)
    return jax.ops.segment_sum(prods, nnz_rows, num_segments=a.m)


def spmm_rowsplit_ref(a: CSR, b: jax.Array, tl: int = 8,
                      l_pad: int | None = None) -> jax.Array:
    """Row-split dataflow reference (paper §4.1), ELL-style padded rows.

    Every row is processed in batches of ``tl`` nonzeroes — the paper's
    "effective number of independent instructions is sensitive to row
    lengths that do not divide 32" (here: that do not divide ``tl``).
    ``l_pad`` is a static upper bound on the row length (defaults to the
    worst case, the whole nnz capacity — callers with host knowledge of the
    max row length should pass it).
    """
    lengths = a.row_lengths()
    if l_pad is None:
        l_pad = int(a.nnz_pad)
    l_pad = max(tl, tl * (-(-l_pad // tl)))
    idx = jnp.arange(l_pad)
    take = a.row_ptr[:-1, None] + idx[None, :]                # (m, l_pad)
    valid = idx[None, :] < lengths[:, None]
    take = jnp.where(valid, take, 0)
    cols = jnp.where(valid, a.col_ind[take], 0)
    vals = jnp.where(valid, a.vals[take], 0)
    return jnp.einsum("ml,mln->mn", vals, b[cols])


def spmm_merge_ref(a: CSR, b: jax.Array, t: int = 8) -> jax.Array:
    """Merge-based (nonzero-split) dataflow reference (paper §4.2).

    Phase 1: equal-nonzero partition.  Phase 2: per-chunk gather + multiply +
    intra-chunk segmented sum.  Epilogue: scatter-add partials into C (the
    carry-out fix-up).
    """
    _, nnz_rows = partition_spmm(a, t)
    rows, local, seg_rows = chunk_segments(nnz_rows, t, a.m)
    n_chunks = rows.shape[0]
    pad = n_chunks * t - a.nnz_pad
    cols = jnp.pad(a.col_ind, (0, pad)).reshape(n_chunks, t)
    vals = jnp.pad(a.vals, (0, pad)).reshape(n_chunks, t)
    prods = vals[..., None] * b[cols]                        # (chunks, t, n)
    # Intra-chunk segmented reduction over the local segment axis.
    onehot = (local[..., None] == jnp.arange(t)[None, None, :])
    partials = jnp.einsum("cts,ctn->csn", onehot.astype(prods.dtype), prods)
    return jax.ops.segment_sum(
        partials.reshape(n_chunks * t, -1), seg_rows.reshape(-1),
        num_segments=a.m)


def _map_leading(one, *stacked):
    """Apply a 2-D-operand reference over folded leading batch dims.

    ``lax.map`` (scan) rather than vmap/moveaxis: the Pallas kernels
    serialize the batch grid axis on a core, so the faithful XLA twin
    iterates batch elements inside one computation too — per-element
    working set, one dispatch — instead of materializing a batch-wide
    gathered intermediate.
    """
    lead = stacked[0].shape[:-2]
    flat = [x.reshape((-1,) + x.shape[-2:]) for x in stacked]
    out = jax.lax.map(one, tuple(flat)) if len(flat) > 1 else \
        jax.lax.map(one, flat[0])
    return out.reshape(lead + out.shape[1:])


def _slot_gather(structure: dict, vals: jax.Array) -> jax.Array:
    """Per-slot values through ``slot_nz`` (sentinel → appended zero) —
    the XLA twin of the kernels' per-call value layout."""
    vals_ext = jnp.concatenate([vals, jnp.zeros((1,), vals.dtype)])
    return vals_ext[structure["slot_nz"]]


def _finish(out, ep, bias_col, res2, out_dtype):
    return apply_epilogue(out, ep, bias_col, res2).astype(out_dtype)


def merge_execute_ref(structure: dict, vals: jax.Array, b: jax.Array,
                      m: int, tm: int, *, epilogue=None, bias=None,
                      residual=None, acc_dtype=jnp.float32,
                      out_dtype=None) -> jax.Array:
    """Plan-execute reference for the merge structure (differentiable XLA).

    Same dataflow as ``merge_spmm_pallas`` on a prebuilt pattern structure:
    gather the raw ``vals`` into chunk slots (``slot_nz``), gather B rows
    per slot, multiply, scatter into C by (tile, lrow) — all in
    ``acc_dtype`` — then apply the fused ``epilogue`` identically to the
    kernel's accumulator flush and cast once to ``out_dtype``.  Unused
    slots carry value 0 and scatter 0.  ``b`` may carry leading batch dims
    — (..., k, n) → (..., m, n), matching the batched kernel grid
    (K-tiling is a VMEM-residency concern with no XLA analogue: the
    compiler owns the streaming here); a flagged ``residual`` batches with
    it.
    """
    acc = jnp.dtype(acc_dtype)
    odt = jnp.promote_types(vals.dtype, b.dtype) if out_dtype is None \
        else jnp.dtype(out_dtype)
    ep = epilogue
    chunk_vals = _slot_gather(structure, vals).astype(acc)
    bias_col = bias.astype(acc)[:, None] \
        if ep is not None and ep.bias else None

    def one(b2, res2=None):
        prods = chunk_vals[..., None] * b2.astype(acc)[structure["cols"]]
        rows = structure["tile"][:, None] * tm + structure["lrow"]
        m_pad = tm * (-(-m // tm))
        out = jax.ops.segment_sum(prods.reshape(-1, b2.shape[-1]),
                                  rows.reshape(-1), num_segments=m_pad)
        return _finish(out[:m], ep, bias_col, res2, odt)

    if b.ndim == 2:
        return one(b, residual)
    if ep is not None and ep.residual:
        return _map_leading(lambda args: one(*args), b, residual)
    return _map_leading(one, b)


def rowsplit_execute_ref(structure: dict, vals: jax.Array,
                         b: jax.Array, m: int, *, epilogue=None, bias=None,
                         residual=None, acc_dtype=jnp.float32,
                         out_dtype=None) -> jax.Array:
    """Plan-execute reference for the ELL structure (differentiable XLA).

    Raw ``vals`` gathered through ``slot_nz`` like the kernel; batched
    like it too: ``b (..., k, n) → (..., m, n)``; fused ``epilogue`` and
    ``acc_dtype``/``out_dtype`` as in ``merge_execute_ref``.
    """
    acc = jnp.dtype(acc_dtype)
    odt = jnp.promote_types(vals.dtype, b.dtype) if out_dtype is None \
        else jnp.dtype(out_dtype)
    ep = epilogue
    ell_vals = _slot_gather(structure, vals).astype(acc)
    bias_col = bias.astype(acc)[:, None] \
        if ep is not None and ep.bias else None

    def one(b2, res2=None):
        out = jnp.einsum("ml,mln->mn", ell_vals,
                         b2.astype(acc)[structure["cols"]])[:m]
        return _finish(out, ep, bias_col, res2, odt)

    if b.ndim == 2:
        return one(b, residual)
    if ep is not None and ep.residual:
        return _map_leading(lambda args: one(*args), b, residual)
    return _map_leading(one, b)


def sddmm_ref(rows: jax.Array, cols: jax.Array, valid: jax.Array,
              dc: jax.Array, b: jax.Array) -> jax.Array:
    """Gather-dot oracle for the sampled dense-dense product.

    ``dvals[..., p] = dC[..., rows[p], :] · B[..., cols[p], :]`` masked by
    ``valid`` — the cotangent of the CSR values under C = A @ B.  Leading
    batch dims are kept per element (shared-values callers reduce them).
    """
    def one(args):
        dc2, b2 = args
        dots = jnp.sum(dc2[rows] * b2[cols], axis=-1)
        return jnp.where(valid, dots, 0).astype(dc.dtype)

    if dc.ndim == 2:
        return one((dc, b))
    return _map_leading(one, dc, b)


def moe_group_gemm_ref(x_sorted: jax.Array, w: jax.Array,
                       group_ids: jax.Array) -> jax.Array:
    """Grouped GEMM oracle: y[i] = x_sorted[i] @ w[group_ids[i]].

    ``x_sorted`` (tokens, d_in) is sorted by expert, ``group_ids`` (tokens,)
    gives each token's expert, ``w`` (experts, d_in, d_out).
    """
    return jnp.einsum("td,tdo->to", x_sorted, w[group_ids])
