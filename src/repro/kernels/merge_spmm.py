"""Merge-based (nonzero-split) SpMM — Pallas TPU kernel.  Paper §4.2.

TPU adaptation of the paper's two-phase decomposition:

* **Phase 1** (``plan_merge_structure``, plain XLA): assign an equal
  number ``T`` of nonzeroes per chunk, *breaking chunks at output row-tile
  boundaries* so every chunk's rows live in exactly one ``TM``-row tile of
  C.  This is the paper's ``PartitionSpmm`` binary search; the
  tile-boundary break replaces the GPU carry-out machinery (CTAs that
  cannot synchronize must ship boundary rows through global memory —
  Pallas grid steps execute in order on a core, so a revisited output
  block simply stays resident in VMEM and accumulates, and the fix-up
  kernel disappears).

* **Phase 2** (``_merge_kernel``): grid ``(batch, n_tiles, chunks,
  k_tiles)``.  A step receives its chunk's column indices, row offsets
  and values in SMEM, one ``(1, 1, T)`` block each, and walks the chunk
  as scalars: each nonzero loads its B row from the VMEM-resident
  ``(TK, TN)`` panel as a dynamic one-row slice — the TPU analogue of the
  paper's row-major coalesced loads (a lane-contiguous 128-wide row) —
  scales it by the value and adds it onto its row of a register-resident
  ``(TM, TN)`` tile, which joins the VMEM accumulator once per step.  The
  chunk stream is ordered by row tile, so C tiles are revisited
  consecutively and flushed exactly once.

Why scalars: Mosaic has no vector gather from VMEM (an in-kernel
``jnp.take`` does not lower), so B rows are addressed by SMEM scalars.
SMEM (1 MiB on a v5e) holds only the current step's blocks and the
prefetched chunk streams (``tile``, ``first``, ``last`` and the per-chunk
slot ``count``, 16 bytes a chunk), so no operand is ever held whole on
chip.  SMEM holds
32-bit scalars, so the values are laid out in slot order per call
(``apply_vals``, one XLA gather) as float32; and a dynamic one-row slice
lowers only on a 32-bit panel, so a 16-bit B is widened to float32 before
the call.  Neither changes the arithmetic: the kernel casts both to the
accumulator dtype anyway.

Two grid axes beyond the paper's decomposition:

* **batch** (leading): one plan executes a whole stack of dense operands
  ``B (batch, k, n)`` in a single dispatch — the plan-once/execute-many
  serving regime with the batch folded into the grid instead of a Python
  loop of launches.
* **k_tiles** (innermost): the dense operand sits in VMEM as one whole
  ``(k, TN)`` panel whenever two float32 copies of it fit the panel's
  share of the TensorCore's VMEM (``tk_max``: every ``k`` up to 98,304 on
  a v5e), so each nonzero costs one scalar trip and B is fetched once per
  lane tile.  A longer ``k`` (or an explicit ``tk``) streams through
  ``(TK, TN)`` panels with the accumulator carried across tiles and the
  column indices outside the resident panel masked per tile, at one trip
  per nonzero per tile.

Latency hiding: the paper's ILP (32 independent loads per thread) becomes
``SLOT_UNROLL`` independent row loads per loop trip plus Mosaic's
double-buffered DMA pipeline across grid steps.  Occupancy (TLP) becomes
grid size.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro import obs
from repro.core.csr import CSR, rows_from_row_ptr
from repro.core.epilogue import apply_epilogue
from repro.obs import scope

# Default tile sizes: TN = 128 lanes (the "warp width" / coalescing unit),
# TM = 8 sublanes.  T, the nonzeroes per chunk (the paper's blockDim.x work
# unit), defaults to about one chunk per row tile (``default_t``), capped at
# T_MAX: a step's three (1, 1, T) SMEM blocks, double-buffered, then stay
# at 24 KiB of the 1 MiB SMEM.
TN = 128
TM = 8
T_MAX = 1024
# Nonzeroes per trip of the kernels' scalar slot loops (``fold_slots``):
# the independent row loads in flight, the paper's per-thread ILP.
SLOT_UNROLL = 8
# VMEM sizing.  The B panel is a float32 (TK, TN) block, double-buffered by
# the Mosaic pipeline; its two buffers may take PANEL_SHARE of the
# TensorCore's VMEM, which leaves the rest for the out, acc, bias and
# residual blocks and Mosaic's own scratch.  Off a TPU the v5e's 128 MiB
# stands in, so interpret mode and the static analyzers model the chip the
# benchmark runs on.  A launch whose blocks, with VMEM_HEADROOM_BYTES to
# spare, outgrow Mosaic's default scoped limit (16 MiB on a v5e, not the
# VMEM) asks for a larger one (``vmem_limit``).
V5E_VMEM_BYTES = 128 * 2 ** 20
PANEL_SHARE = (3, 4)
DEFAULT_SCOPED_VMEM_BYTES = 16 * 2 ** 20
VMEM_HEADROOM_BYTES = 4 * 2 ** 20


def vmem_capacity_bytes() -> int:
    """The TensorCore's VMEM: read from the chip on a TPU backend, else
    the v5e's."""
    if jax.default_backend() != "tpu":
        return V5E_VMEM_BYTES
    return pltpu.get_tpu_info().vmem_capacity_bytes


def panel_rows(vmem_bytes: int, *, sub: int = 8) -> int:
    """Most rows (a sublane multiple) of a float32 ``(rows, TN)`` panel
    whose two buffers fit ``PANEL_SHARE`` of ``vmem_bytes``."""
    num, den = PANEL_SHARE
    return vmem_bytes * num // den // (2 * TN * 4) // sub * sub


# K-tile cap on a v5e: 96 MiB of its 128 MiB VMEM holds two 98,304-row
# panels, so every d_in of ``repro.configs`` (Qwen2-72B's 29,568: 14.4 MiB a
# buffer) and the graph benchmark's 65,536 runs as one K-tile.
DEFAULT_TK_MAX = panel_rows(V5E_VMEM_BYTES)


def tk_max() -> int:
    """The K-tile cap of the chip this process compiles for."""
    return panel_rows(vmem_capacity_bytes())


def resolve_tk(k: int, tk: int | None, *, sub: int = 8) -> tuple[int, int]:
    """Resolve the K-tile size: returns ``(tk, n_k)``.

    ``tk`` is clamped to a sublane multiple and to the (padded) ``k``;
    ``None`` picks the whole of ``k`` up to ``tk_max()``, so the panel stays
    resident (one K-tile, one trip per nonzero) wherever VMEM holds it and
    a larger ``k`` streams in panels of the cap.
    """
    k_pad = max(sub, sub * (-(-k // sub)))
    if tk is None:
        tk = min(k_pad, tk_max())
    else:
        tk = min(max(sub, sub * (-(-tk // sub))), k_pad)
    return tk, -(-k_pad // tk)


def launch_vmem_bytes(tk: int, *, tm: int, tn: int, out_dtype, acc_dtype,
                      bias_dtype=None, residual_dtype=None) -> int:
    """Modeled VMEM of one SpMM launch: the float32 B panel, out, bias and
    residual blocks double-buffered and the accumulator once — the blocks
    the static launch models (``launch_models``, ``ell_launch``) count."""
    size = lambda shape, dt: int(np.prod(shape)) * jnp.dtype(dt).itemsize
    total = 2 * size((tk, tn), jnp.float32) + 2 * size((tm, tn), out_dtype)
    total += size((tm, tn), acc_dtype)
    if bias_dtype is not None:
        total += 2 * size((tm, 1), bias_dtype)
    if residual_dtype is not None:
        total += 2 * size((tm, tn), residual_dtype)
    return total


def vmem_limit(blocks_bytes: int) -> int | None:
    """The scoped VMEM limit a launch asks for: None (Mosaic's default)
    while its blocks fit that with the headroom, else the blocks plus the
    headroom, at most the VMEM."""
    need = blocks_bytes + VMEM_HEADROOM_BYTES
    if need <= DEFAULT_SCOPED_VMEM_BYTES:
        return None
    return min(need, vmem_capacity_bytes())


# Kernel launches by K-tile count, counted where a launch is traced (a jit
# cache hit, such as a second launch of one shape in a program, is not a
# trace), never per call: a jitted program adds nothing to a timed window.
_k_tiles_total = obs.registry.counter(
    "spmm_kernel_k_tiles_total",
    "SpMM kernel launches traced, by kernel and K-tile count",
    labels=("kernel", "k_tiles"))


def launch_params(kernel: str, tk: int, n_k: int, *, tm: int, tn: int,
                  out_dtype, acc_dtype, ep, bias, residual):
    """Count a traced launch of ``kernel`` in ``spmm_kernel_k_tiles_total``
    and return its ``compiler_params``: a raised scoped VMEM limit where
    its blocks need one, else None."""
    _k_tiles_total.labels(kernel=kernel, k_tiles=str(n_k)).inc()
    limit = vmem_limit(launch_vmem_bytes(
        tk, tm=tm, tn=tn, out_dtype=out_dtype, acc_dtype=acc_dtype,
        bias_dtype=bias.dtype if ep is not None and ep.bias else None,
        residual_dtype=residual.dtype if ep is not None and ep.residual
        else None))
    return None if limit is None else pltpu.CompilerParams(
        vmem_limit_bytes=limit)


def default_t(m: int, nnz_pad: int, *, tm: int = TM) -> int:
    """Default nonzeroes per chunk for an ``(m, ·)`` pattern.

    The mean nonzero count of a ``tm``-row tile, rounded up to a power of
    two in ``[8, T_MAX]``: one chunk per tile on average, so short-row
    patterns do not pad every tile out to a long chunk and long-row
    patterns take ``T_MAX`` nonzeroes per grid step.  Shapes only, so it
    also resolves under trace.
    """
    per_tile = -(-nnz_pad // max(1, -(-m // tm)))
    return int(min(T_MAX, max(8, 1 << max(0, per_tile - 1).bit_length())))


def plan_merge_structure(a: CSR, *, t: int, tm: int = TM):
    """Phase 1, pattern-only: equal-nonzero chunks broken at TM-row tiles.

    Depends only on the sparsity pattern (``row_ptr``/``col_ind``), never on
    ``vals`` — the plan-once/execute-many split: values are re-applied per
    call through ``slot_nz`` while the chunk structure is built once per
    pattern (``repro.core.plan``).

    Returns a dict of device arrays (all static-shaped):
      cols    (C, t) int32  column index of each nonzero in each chunk
      lrow    (C, t) int32  row offset within the TM-row tile, in [0, tm)
      slot_nz (C, t) int32  flat nonzero id feeding each slot, or ``nnz_pad``
                            (a sentinel gathering an appended zero) for
                            unused slots
      tile    (C,)   int32  output row-tile of the chunk (non-decreasing)
      first  (C,)   int32   1 iff chunk is the first of its row tile
    where C = nnz_pad//t + ceil(m/tm) (static worst case).
    """
    m = a.m
    nnz_pad = a.nnz_pad
    if m == 0:
        # Degenerate 0-row pattern: no output tiles, no valid nonzeroes.
        # Execution early-outs before touching these (ops.merge_execute),
        # but the structure must still be constructible with static shapes.
        n_chunks = max(1, -(-nnz_pad // t))
        zeros = jnp.zeros((n_chunks, t), jnp.int32)
        edge = jnp.zeros((n_chunks,), jnp.int32)
        return dict(cols=zeros, lrow=zeros,
                    slot_nz=jnp.full((n_chunks, t), nnz_pad, jnp.int32),
                    tile=edge, first=edge.at[0].set(1),
                    last=edge.at[-1].set(1))
    n_tiles_m = -(-m // tm)
    n_chunks = -(-nnz_pad // t) + n_tiles_m

    rows = rows_from_row_ptr(a.row_ptr, nnz_pad)   # (nnz,) row ids, pad→m
    tile_of_nz = jnp.minimum(rows // tm, n_tiles_m - 1)    # pad entries clamp
    # first nonzero and nonzero count per row tile (tile_of_nz is
    # non-decreasing: CSR order, pads at the end).
    tile_starts = jnp.searchsorted(
        tile_of_nz, jnp.arange(n_tiles_m, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    tile_counts = jnp.diff(jnp.append(tile_starts, nnz_pad))
    # chunks allocated per tile: ceil(count/t), min 1 so that every C row
    # tile is visited (and zeroed) at least once; exclusive prefix sum.
    chunks_per_tile = jnp.maximum(1, -(-tile_counts // t))
    chunks_before = jnp.cumsum(chunks_per_tile) - chunks_per_tile

    # chunk -> row tile (non-decreasing); unused tail chunks point at the
    # last used tile so the revisit stream stays monotone.
    chunk_ids = jnp.arange(n_chunks, dtype=jnp.int32)
    cum = chunks_before + chunks_per_tile  # inclusive prefix
    tile_of_chunk = jnp.searchsorted(cum, chunk_ids, side="right")
    used = chunk_ids < cum[-1]
    tile_of_chunk = jnp.minimum(tile_of_chunk, n_tiles_m - 1)
    tile = jnp.where(used, tile_of_chunk, n_tiles_m - 1).astype(jnp.int32)

    # Slot s of chunk c holds nonzero number (c - chunks_before[tile]) * t
    # + s of its row tile, so each slot gathers its nonzero.  Padded
    # nonzeroes (past ``nnz``) keep their formula slots (reserved via
    # tile_counts of the last tile) but read as empty, like the slots past
    # a tile's count.
    pos = ((chunk_ids - chunks_before[tile])[:, None] * t
           + jnp.arange(t, dtype=jnp.int32)[None, :])
    nz = tile_starts[tile][:, None] + pos
    live = (pos < tile_counts[tile][:, None]) & (nz < a.nnz())
    slot_nz = jnp.where(live, nz, nnz_pad).astype(jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)      # the sentinel slot's entry
    cols = jnp.concatenate([a.col_ind.astype(jnp.int32), zero])[slot_nz]
    lrow = jnp.concatenate([rows % tm, zero])[slot_nz]
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (tile[1:] != tile[:-1]).astype(jnp.int32)])
    last = jnp.concatenate(
        [(tile[1:] != tile[:-1]).astype(jnp.int32),
         jnp.ones((1,), jnp.int32)])
    return dict(cols=cols, lrow=lrow, slot_nz=slot_nz, tile=tile, first=first,
                last=last)


def apply_vals(structure: dict, vals: jax.Array) -> jax.Array:
    """Gather per-call values into a structure's slots (chunk or ELL layout).

    ``slot_nz == nnz_pad`` slots read the appended zero, so padded/unused
    slots contribute nothing regardless of what ``vals`` holds.
    """
    vals_ext = jnp.concatenate([vals, jnp.zeros((1,), vals.dtype)])
    return vals_ext[structure["slot_nz"]]


def plan_merge(a: CSR, *, t: int, tm: int = TM):
    """Phase 1 with values applied: the single-call (plan-per-call) form."""
    structure = plan_merge_structure(a, t=t, tm=tm)
    plan = dict(structure)
    plan["vals"] = apply_vals(structure, a.vals)
    return plan


# --------------------------------------------------- shared kernel pieces ---


def fold_slots(n, body, carry, *, unroll: int = SLOT_UNROLL):
    """``carry = body(s, carry)`` for slots ``s`` in ``[0, n)``.

    ``unroll`` slots per loop trip (Mosaic lowers only fully unrolled or
    plain ``fori_loop``s, so the unroll is written out), then the
    remainder one at a time.  ``n`` may be a traced scalar (a chunk's
    slot count read from SMEM).
    """
    def trip(g, c):
        for u in range(unroll):
            c = body(g * unroll + u, c)
        return c

    full = n // unroll
    carry = jax.lax.fori_loop(0, full, trip, carry)
    return jax.lax.fori_loop(full * unroll, n, body, carry)


def scaled_b_row(b_ref, col, val, kk, *, tk: int, n_k: int, acc_dtype):
    """``val * B[col, :]`` as a ``(1, TN)`` row in ``acc_dtype``.

    Read from the resident ``(TK, TN)`` panel of k-tile ``kk`` as a
    dynamic one-row slice.  A column outside the panel contributes zero
    on this step; the accumulator carry picks it up when its panel
    streams in.
    """
    if n_k > 1:
        local = col - kk * tk
        inside = (local >= 0) & (local < tk)
        val = jnp.where(inside, val, 0)
        col = jnp.where(inside, local, 0)
    row = b_ref[0, pl.ds(col, 1), :]
    return val.astype(acc_dtype) * row.astype(acc_dtype)


def split_refs(rest, ep):
    """``(bias_ref, res_ref, o_ref, acc_ref)`` from the refs after B; the
    epilogue operands are present exactly per ``ep``'s flags."""
    i = 0
    bias_ref = res_ref = None
    if ep is not None and ep.bias:
        bias_ref, i = rest[i], i + 1
    if ep is not None and ep.residual:
        res_ref, i = rest[i], i + 1
    return bias_ref, res_ref, rest[i], rest[i + 1]


def flush_tile(acc_ref, o_ref, ep, bias_ref, res_ref) -> None:
    """Write the accumulator once, with the fused epilogue: one pass over
    C instead of a write + re-read for bias/activation/residual."""
    r = apply_epilogue(
        acc_ref[...], ep,
        bias_ref[...] if bias_ref is not None else None,
        res_ref[0] if res_ref is not None else None)
    o_ref[0] = r.astype(o_ref.dtype)


def smem_vals(structure: dict, vals: jax.Array) -> jax.Array:
    """The per-call values in the structure's slot layout, as the float32
    scalars the kernels read from SMEM."""
    return apply_vals(structure, vals.astype(jnp.float32))


def chunk_counts(slot_nz: jax.Array, nnz_pad: int) -> jax.Array:
    """Per chunk, one past its last live slot: the kernel's trip count,
    so the sentinel tail of a tile's last chunk costs no loop trips."""
    t = slot_nz.shape[1]
    live = slot_nz < nnz_pad
    return jnp.max(jnp.where(live, jnp.arange(1, t + 1, dtype=jnp.int32),
                             0), axis=1).astype(jnp.int32)


# ------------------------------------------------------------- the kernel ---


def _merge_kernel(tile_ref, first_ref, last_ref, count_ref, cols_ref,
                  lrow_ref, vals_ref, b_ref, *rest, tk: int, n_k: int,
                  acc_dtype, ep):
    bias_ref, res_ref, o_ref, acc_ref = split_refs(rest, ep)
    c = pl.program_id(2)
    kk = pl.program_id(3)

    @pl.when((first_ref[c] == 1) & (kk == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The chunk folds into a register-resident (TM, TN) tile: each scaled
    # B row lands on its sublane through a select, the in-register form
    # of the scatter into the C tile.
    sub = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)

    def slot(s, acc):
        row = scaled_b_row(b_ref, cols_ref[0, 0, s], vals_ref[0, 0, s], kk,
                           tk=tk, n_k=n_k, acc_dtype=acc_dtype)
        return acc + jnp.where(sub == lrow_ref[0, 0, s], row, 0)

    acc_ref[...] += fold_slots(count_ref[c], slot,
                               jnp.zeros(acc_ref.shape, acc_dtype))

    @pl.when((last_ref[c] == 1) & (kk == n_k - 1))
    def _flush():
        flush_tile(acc_ref, o_ref, ep, bias_ref, res_ref)


def merge_spmm_pallas(plan: dict, vals: jax.Array, b: jax.Array,
                      m_pad: int, *, tm: int = TM, tn: int = TN,
                      tk: int | None = None, interpret: bool = False,
                      acc_dtype=jnp.float32, out_dtype=None,
                      epilogue=None, bias=None,
                      residual=None) -> jax.Array:
    """Phase 2. ``b`` is (batch, k, n), n % tn == 0, m_pad % tm == 0.

    ``plan`` is the pattern structure (``plan_merge_structure``); ``vals``
    the raw (nnz_pad,) value vector, laid out per chunk through
    ``slot_nz``.  ``epilogue`` (a ``repro.core.Epilogue``) fuses
    ``act(C + bias) * scale + residual`` into the accumulator flush —
    ``bias (m_pad,)`` and ``residual (batch, m_pad, n)`` must be present
    exactly per its flags.  Accumulation runs in ``acc_dtype`` (f32 by
    default, also under bf16 inputs); C is written once in ``out_dtype``
    (default: b's dtype).

    Returns (batch, m_pad, n): the batch rides the leading grid axis (one
    dispatch for the whole stack) and B sits in VMEM as one whole-``k``
    panel up to ``tk_max()`` rows, else streams in (TK, TN) panels.
    Each grid step DMAs only its own chunk of indices and values into
    SMEM; the chunk-to-tile stream, the first/last flags and the per-chunk
    slot counts are scalar-prefetched (16 bytes a chunk of the 1 MiB
    SMEM).
    """
    out_dtype = b.dtype if out_dtype is None else out_dtype
    with scope("widen_b"):
        b = b.astype(jnp.float32)   # one-row slices need a 32-bit panel
    batch, k, n = b.shape
    n_chunks, t = plan["cols"].shape
    tk, n_k = resolve_tk(k, tk)
    kpad = n_k * tk - k
    if kpad:
        with scope("pad"):
            b = jnp.pad(b, ((0, 0), (0, kpad), (0, 0)))
    with scope("gather_vals"):
        count = chunk_counts(plan["slot_nz"], vals.shape[0])
        per_chunk = [x.reshape(n_chunks, 1, t) for x in
                     (plan["cols"], plan["lrow"], smem_vals(plan, vals))]
    ep = epilogue
    grid = (batch, n // tn, n_chunks, n_k)
    chunk_spec = pl.BlockSpec((1, 1, t), lambda bb, j, c, kk, *_: (c, 0, 0),
                              memory_space=pltpu.SMEM)
    in_specs = [chunk_spec] * 3 + [
        pl.BlockSpec((1, tk, tn), lambda bb, j, c, kk, *_: (bb, kk, j)),
    ]
    operands = [*per_chunk, b]
    if ep is not None and ep.bias:
        in_specs.append(pl.BlockSpec(
            (tm, 1), lambda bb, j, c, kk, tile, *_: (tile[c], 0)))
        operands.append(bias.reshape(m_pad, 1))
    if ep is not None and ep.residual:
        in_specs.append(pl.BlockSpec(
            (1, tm, tn), lambda bb, j, c, kk, tile, *_: (bb, tile[c], j)))
        operands.append(residual)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, tm, tn), lambda bb, j, c, kk, tile, *_: (bb, tile[c], j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), acc_dtype)],
    )
    kernel = functools.partial(_merge_kernel, tk=tk, n_k=n_k,
                               acc_dtype=acc_dtype, ep=ep)
    params = launch_params("merge_spmm", tk, n_k, tm=tm, tn=tn,
                           out_dtype=out_dtype, acc_dtype=acc_dtype, ep=ep,
                           bias=bias, residual=residual)
    with scope("kernel"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((batch, m_pad, n), out_dtype),
            compiler_params=params,
            interpret=interpret,
            name="merge_spmm",
        )(plan["tile"], plan["first"], plan["last"], count, *operands)


# ----------------------------------------------------- static launch model ---


def launch_models(plan, n, batch, var, tk):
    """Static model of ``merge_spmm_pallas``'s one launch.

    Mirrors the grid/BlockSpec construction above block-for-block (a
    drifted model fails the kernel audit's in-bounds/single-writer
    enumeration, which evaluates these maps against the real scalar
    streams).  ``plan`` carries ``.meta``/``.fwd``; ``var`` the dtype/
    epilogue corner (see ``repro.kernels.introspect``).
    """
    from .introspect import KernelBlock, KernelLaunch
    meta, fwd = plan.meta, plan.fwd
    c_n, t = fwd["cols"].shape
    tile = np.asarray(fwd["tile"])
    last = np.asarray(fwd["last"])
    tk, n_k = resolve_tk(meta.k, tk)
    m_pad = TM * (-(-meta.m // TM))
    ep = var.epilogue
    odt = var.out_dtype or var.b_dtype
    chunk = lambda bb, j, c, kk: (c, 0, 0)
    blocks = [
        KernelBlock("tile", (c_n,), "int32", None, (c_n,), "scalar"),
        KernelBlock("first", (c_n,), "int32", None, (c_n,), "scalar"),
        KernelBlock("last", (c_n,), "int32", None, (c_n,), "scalar"),
        KernelBlock("count", (c_n,), "int32", None, (c_n,), "scalar"),
        KernelBlock("cols", (1, 1, t), "int32", chunk, (c_n, 1, t), "in",
                    "smem"),
        KernelBlock("lrow", (1, 1, t), "int32", chunk, (c_n, 1, t), "in",
                    "smem"),
        KernelBlock("vals", (1, 1, t), "float32", chunk, (c_n, 1, t),
                    "in", "smem"),
        KernelBlock("b", (1, tk, TN), "float32",
                    lambda bb, j, c, kk: (bb, kk, j),
                    (batch, n_k * tk, n), "in"),
    ]
    if ep is not None and ep.bias:
        blocks.append(KernelBlock(
            "bias", (TM, 1), var.b_dtype,
            lambda bb, j, c, kk: (tile[c], 0), (m_pad, 1), "in"))
    if ep is not None and ep.residual:
        blocks.append(KernelBlock(
            "residual", (1, TM, TN), var.b_dtype,
            lambda bb, j, c, kk: (bb, tile[c], j),
            (batch, m_pad, n), "in"))
    out = KernelBlock("out", (1, TM, TN), odt,
                      lambda bb, j, c, kk: (bb, tile[c], j),
                      (batch, m_pad, n), "out")
    blocks += [out, KernelBlock("acc", (TM, TN), var.acc_dtype, None,
                                (TM, TN), "scratch")]
    return [KernelLaunch(
        label="merge", grid=(batch, n // TN, c_n, n_k),
        blocks=tuple(blocks),
        flush=lambda bb, j, c, kk: bool(last[c] == 1) and kk == n_k - 1,
        out=out)]
