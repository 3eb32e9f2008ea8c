"""Row-splitting SpMM — Pallas TPU kernel.  Paper §4.1.

TPU adaptation of the paper's warp-per-row kernel:

* The GPU warp's 32 lanes reading 32 consecutive floats of a row-major B row
  become a ``TN=128``-lane row slice of B read from a VMEM-resident
  ``(TK, TN)`` panel — the dense operand streams through VMEM in K tiles
  with the accumulator carried across them (grid axis ``k_tiles``,
  innermost), so VMEM stays bounded at any ``k``; a leading ``batch`` grid
  axis executes a whole stack of dense operands per dispatch (see
  ``merge_spmm`` for the shared rationale).
* "Equal rows per processor" becomes a grid over ``TM``-row tiles of C; each
  row is ELL-padded to the static bound ``L`` (the longest row) and walked
  in windows of up to ``L_WINDOW`` slots per grid step — the TPU
  manifestation of the paper's Type 2 load imbalance: every row tile takes
  the grid steps of the matrix's longest row, short rows leave them idle,
  and the waste grows with row irregularity exactly as in Fig. 4.
* The warp ``__shfl`` broadcast of ``(col_ind, val)`` becomes SMEM scalars:
  each step receives its ``(TM, window)`` index/value block in SMEM and
  walks each row's live slots (the per-row length is scalar-prefetched),
  so ELL padding inside a window costs no loop trips.  As in
  ``merge_spmm``, Mosaic has no in-kernel vector gather, so B rows are
  addressed by these scalars.

Phase 0 (``plan_rowsplit``, plain XLA): scatter CSR into ELL-padded
``(m, L)`` index/value arrays.  This is *runtime scratch within the same
jit*, not a stored format conversion — the input stays CSR (the paper's
headline constraint).
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.csr import CSR

from .merge_spmm import (TM, TN, apply_vals, flush_tile, fold_slots,
                         resolve_tk, scaled_b_row, smem_vals, split_refs)

DEFAULT_TL = 16
# Most ELL slots per grid step: a (TM, L_WINDOW) int32 index block and its
# float32 values, double-buffered, take 64 KiB of the 1 MiB SMEM.
L_WINDOW = 512


def ell_slots(a: CSR, rows: jax.Array, l: int, *, tm: int = TM) -> dict:
    """ELL slot block ``{cols, slot_nz}`` for a row subset, padded to tm.

    ``rows`` (r,) int32 selects the rows; each is laid out over ``l``
    slots.  Invalid slots carry ``slot_nz == nnz_pad`` — the sentinel
    that reads the appended zero in ``merge_spmm.apply_vals`` — and the
    column gather is sentinel-extended so a 0-nnz pattern (empty
    ``col_ind``) stays constructible.  Shared by the whole-matrix
    row-split structure and the per-bucket row-grouped structure
    (``rowgroup_spmm``) so the subtle slot/sentinel contract lives once.
    """
    lengths = jnp.diff(a.row_ptr)
    idx = jnp.arange(l, dtype=jnp.int32)
    take = a.row_ptr[rows][:, None] + idx[None, :]         # (r, l)
    valid = idx[None, :] < lengths[rows][:, None]
    safe = jnp.where(valid, take, 0)
    col_ext = jnp.concatenate(
        [a.col_ind, jnp.zeros((1,), a.col_ind.dtype)])
    cols = jnp.where(valid, col_ext[safe], 0)
    slot_nz = jnp.where(valid, take, a.nnz_pad).astype(jnp.int32)
    r = rows.shape[0]
    pad_rows = tm * (-(-r // tm)) - r
    cols = jnp.pad(cols, ((0, pad_rows), (0, 0)))
    slot_nz = jnp.pad(slot_nz, ((0, pad_rows), (0, 0)),
                      constant_values=a.nnz_pad)
    return dict(cols=cols, slot_nz=slot_nz)


def plan_rowsplit_structure(a: CSR, *, l_pad: int, tl: int = DEFAULT_TL,
                            tm: int = TM):
    """Phase 0, pattern-only: ELL slot structure (m_pad, L), L = l_pad↑tl.

    ``l_pad`` must be a static upper bound on the longest row.  Depends only
    on the sparsity pattern; per-call values are re-applied through
    ``slot_nz`` (see ``merge_spmm.apply_vals``) — the plan-once/execute-many
    split of ``repro.core.plan``.
    """
    l = max(tl, tl * (-(-l_pad // tl)))
    rows = jnp.arange(a.m, dtype=jnp.int32)
    return ell_slots(a, rows, l, tm=tm)


def plan_rowsplit(a: CSR, *, l_pad: int, tl: int = DEFAULT_TL,
                  tm: int = TM):
    """Phase 0 with values applied: the single-call (plan-per-call) form."""
    structure = plan_rowsplit_structure(a, l_pad=l_pad, tl=tl, tm=tm)
    plan = dict(structure)
    plan["vals"] = apply_vals(structure, a.vals)
    return plan


def slot_window(l: int) -> int:
    """ELL slots a grid step walks: the whole row block up to
    ``L_WINDOW``, else ``L_WINDOW`` (the block is padded to a multiple).
    Either way the ``(TM, window)`` SMEM blocks meet Mosaic's tiling rule
    (last dim a multiple of 128 or the whole array)."""
    return l if l <= L_WINDOW else L_WINDOW


def row_lengths(slot_nz: jax.Array, nnz_pad: int) -> jax.Array:
    """Live slots per ELL row (slots fill from 0): the per-row trip
    count, so a row's ELL padding costs no loop trips."""
    return jnp.sum(slot_nz < nnz_pad, axis=1, dtype=jnp.int32)


def _rowsplit_kernel(len_ref, cols_ref, vals_ref, b_ref, *rest, tm: int,
                     tw: int, tk: int, n_k: int, acc_dtype, ep):
    bias_ref, res_ref, o_ref, acc_ref = split_refs(rest, ep)
    i = pl.program_id(1)
    ll = pl.program_id(3)
    kk = pl.program_id(4)
    n_l = pl.num_programs(3)

    @pl.when((ll == 0) & (kk == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # One row at a time, its window of slots folded into a (1, TN)
    # register accumulator — the warp-per-row kernel with the warp's
    # lane-parallel B row loads as 128-lane row slices — then selected
    # onto its sublane of the register-resident (TM, TN) tile.
    sub = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)

    def row(r, acc):
        n_r = jnp.clip(len_ref[i * tm + r] - ll * tw, 0, tw)

        def slot(s, racc):
            return racc + scaled_b_row(b_ref, cols_ref[r, s],
                                       vals_ref[r, s], kk, tk=tk, n_k=n_k,
                                       acc_dtype=acc_dtype)

        racc = fold_slots(n_r, slot,
                          jnp.zeros((1, acc_ref.shape[1]), acc_dtype))
        return acc + jnp.where(sub == r, racc, 0)

    acc_ref[...] += jax.lax.fori_loop(0, tm, row,
                                      jnp.zeros(acc_ref.shape, acc_dtype))

    @pl.when((ll == n_l - 1) & (kk == n_k - 1))
    def _flush():
        flush_tile(acc_ref, o_ref, ep, bias_ref, res_ref)


def rowsplit_spmm_pallas(plan: dict, vals: jax.Array, b: jax.Array, *,
                         tm: int = TM, tn: int = TN,
                         tk: int | None = None, interpret: bool = False,
                         acc_dtype=jnp.float32, out_dtype=None,
                         epilogue=None, bias=None,
                         residual=None) -> jax.Array:
    """``b`` is (batch, k, n) with n % tn == 0; plan arrays (m_pad, L).

    ``plan`` is the pattern structure (``plan_rowsplit_structure``);
    ``vals`` the raw (nnz_pad,) values, laid out in ELL slots through
    ``slot_nz``.  ``epilogue``/``bias (m_pad,)``/``residual
    (batch, m_pad, n)`` fuse the C tail into the accumulator flush;
    ``acc_dtype``/``out_dtype`` control accumulation and output precision
    (see ``merge_spmm_pallas``).

    Returns (batch, m_pad, n): batch on the leading grid axis, B streamed
    through VMEM in (TK, TN) panels (``k_tiles`` innermost, accumulator
    carried).  Each step DMAs a ``(TM, window)`` block of column indices
    and values into SMEM; the per-row lengths are scalar-prefetched.
    """
    out_dtype = b.dtype if out_dtype is None else out_dtype
    b = b.astype(jnp.float32)       # one-row slices need a 32-bit panel
    batch, k, n = b.shape
    m_pad, l = plan["cols"].shape
    tk, n_k = resolve_tk(k, tk)
    kpad = n_k * tk - k
    if kpad:
        b = jnp.pad(b, ((0, 0), (0, kpad), (0, 0)))
    tw = slot_window(l)
    lens = row_lengths(plan["slot_nz"], vals.shape[0])
    slots = [plan["cols"], smem_vals(plan, vals)]
    if l % tw:
        slots = [jnp.pad(x, ((0, 0), (0, tw - l % tw))) for x in slots]
    n_l = slots[0].shape[1] // tw
    ep = epilogue
    grid = (batch, m_pad // tm, n // tn, n_l, n_k)
    slot_spec = pl.BlockSpec((tm, tw), lambda bb, i, j, ll, kk, lens:
                             (i, ll), memory_space=pltpu.SMEM)
    in_specs = [slot_spec, slot_spec,
                pl.BlockSpec((1, tk, tn), lambda bb, i, j, ll, kk, lens:
                             (bb, kk, j))]
    operands = [*slots, b]
    if ep is not None and ep.bias:
        in_specs.append(pl.BlockSpec((tm, 1), lambda bb, i, j, ll, kk, lens:
                                     (i, 0)))
        operands.append(bias.reshape(m_pad, 1))
    if ep is not None and ep.residual:
        in_specs.append(pl.BlockSpec((1, tm, tn),
                                     lambda bb, i, j, ll, kk, lens:
                                     (bb, i, j)))
        operands.append(residual)
    kernel = functools.partial(_rowsplit_kernel, tm=tm, tw=tw, tk=tk,
                               n_k=n_k, acc_dtype=acc_dtype, ep=ep)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, tm, tn),
                                   lambda bb, i, j, ll, kk, lens:
                                   (bb, i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), acc_dtype)]),
        out_shape=jax.ShapeDtypeStruct((batch, m_pad, n), out_dtype),
        interpret=interpret,
    )(lens, *operands)


# ----------------------------------------------------- static launch model ---


def ell_launch(label, meta, slot_shape, n, batch, var, tk, *,
               with_bias, with_residual, out_dtype):
    """One row-split-kernel launch over an (m_pad, L) ELL block — shared
    by the rowsplit method and rowgroup's per-group launches.  Mirrors
    ``rowsplit_spmm_pallas``'s grid/BlockSpec construction block-for-
    block (see ``repro.kernels.introspect``)."""
    from .introspect import KernelBlock, KernelLaunch
    m_pad, length = slot_shape
    tw = slot_window(length)
    n_l = -(-length // tw)
    tk, n_k = resolve_tk(meta.k, tk)
    slot_map = lambda bb, i, j, ll, kk: (i, ll)
    blocks = [
        KernelBlock("lens", (m_pad,), "int32", None, (m_pad,), "scalar"),
        KernelBlock("cols", (TM, tw), "int32", slot_map,
                    (m_pad, n_l * tw), "in", "smem"),
        KernelBlock("vals", (TM, tw), "float32", slot_map,
                    (m_pad, n_l * tw), "in", "smem"),
        KernelBlock("b", (1, tk, TN), "float32",
                    lambda bb, i, j, ll, kk: (bb, kk, j),
                    (batch, n_k * tk, n), "in"),
    ]
    if with_bias:
        blocks.append(KernelBlock(
            "bias", (TM, 1), var.b_dtype,
            lambda bb, i, j, ll, kk: (i, 0), (m_pad, 1), "in"))
    if with_residual:
        blocks.append(KernelBlock(
            "residual", (1, TM, TN), var.b_dtype,
            lambda bb, i, j, ll, kk: (bb, i, j),
            (batch, m_pad, n), "in"))
    out = KernelBlock("out", (1, TM, TN), out_dtype,
                      lambda bb, i, j, ll, kk: (bb, i, j),
                      (batch, m_pad, n), "out")
    blocks += [out, KernelBlock("acc", (TM, TN), var.acc_dtype, None,
                                (TM, TN), "scratch")]
    return KernelLaunch(
        label=label,
        grid=(batch, m_pad // TM, n // TN, n_l, n_k),
        blocks=tuple(blocks),
        flush=lambda bb, i, j, ll, kk: ll == n_l - 1 and kk == n_k - 1,
        out=out)


def launch_models(plan, n, batch, var, tk):
    """Static model of ``rowsplit_spmm_pallas``'s one launch."""
    ep = var.epilogue
    return [ell_launch(
        "rowsplit", plan.meta, tuple(plan.fwd["slot_nz"].shape),
        n, batch, var, tk,
        with_bias=ep is not None and ep.bias,
        with_residual=ep is not None and ep.residual,
        out_dtype=var.out_dtype or var.b_dtype)]
