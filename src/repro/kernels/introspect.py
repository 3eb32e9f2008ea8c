"""Static launch models: each kernel describes its own ``pallas_call``.

A :class:`KernelLaunch` is a host-side, numerically enumerable model of
one ``pallas_call`` — the grid, every BlockSpec (shape, dtype, index
map over grid points, full operand shape, in/out/scratch/scalar kind)
and the accumulator-flush predicate.  Each kernel module exports a
``launch_models(plan, n, batch, var, tk)`` hook built from these (wired
into the registry through ``MethodSpec.traffic``), so the static
analyses — the kernel audit's VMEM/bounds/single-writer checks
(``repro.analysis.kernel_audit``), the coalescing checker
(``repro.analysis.access``) and the bytes-moved analyzer
(``repro.analysis.traffic``) — all read one model that lives next to
the ``pl.BlockSpec`` lines it mirrors.

``var`` is any object with ``vals_dtype``/``b_dtype``/``acc_dtype``/
``out_dtype``/``epilogue`` attributes (e.g. ``kernel_audit.Variant``).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable

import numpy as np


@dataclasses.dataclass(frozen=True)
class KernelBlock:
    """One BlockSpec of a modeled launch (or a scratch/scalar operand)."""

    name: str
    shape: tuple                 # block shape
    dtype: str
    index_map: Callable | None   # grid point -> block index, or None
    array_shape: tuple           # full operand shape
    kind: str                    # "in" | "out" | "scratch" | "scalar"
    space: str = "vmem"          # on-chip home of the block: "vmem" |
                                 # "smem" (scalar-prefetch is always smem)

    def nbytes(self) -> int:
        import jax.numpy as jnp
        n = int(np.prod(self.shape)) if self.shape else 1
        return n * jnp.dtype(self.dtype).itemsize

    def array_nbytes(self) -> int:
        import jax.numpy as jnp
        n = int(np.prod(self.array_shape)) if self.array_shape else 1
        return n * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class KernelLaunch:
    """A statically checkable model of one ``pallas_call``."""

    label: str
    grid: tuple
    blocks: tuple                # KernelBlock, ... (includes the out block)
    flush: Callable              # grid point -> bool (writes out block?)
    out: KernelBlock

    def vmem_bytes(self) -> int:
        """Modeled VMEM residency: in/out blocks double-buffered (the
        Mosaic DMA pipeline), scratch counted once."""
        return sum(b.nbytes() * (2 if b.kind in ("in", "out") else 1)
                   for b in self.blocks
                   if b.space == "vmem" and b.kind != "scalar")

    def smem_bytes(self) -> int:
        """Modeled SMEM residency: scalar-prefetch operands whole, SMEM
        in-blocks double-buffered."""
        return sum(b.array_nbytes() if b.kind == "scalar"
                   else 2 * b.nbytes() for b in self.blocks
                   if b.kind == "scalar" or b.space == "smem")

    def hbm_bytes(self) -> int:
        """Transition-counted DMA traffic of the launch.

        Walks the grid in lexicographic order (last axis fastest — the
        Pallas TPU iteration order) and counts an input-block fetch only
        when its block index differs from the previous step's (Mosaic
        elides the copy when the index is unchanged).  Output tiles are
        written at each flush point; scalar-prefetch operands are read
        once, whole; scratch never touches HBM.
        """
        total = 0
        for blk in self.blocks:
            if blk.kind == "scalar":
                total += blk.array_nbytes()
            elif blk.kind == "in":
                total += self._fetches(blk) * blk.nbytes()
        writes = sum(1 for p in np.ndindex(*self.grid) if self.flush(*p))
        return total + writes * self.out.nbytes()

    def _fetches(self, blk: KernelBlock) -> int:
        if blk.index_map is None:
            return 1
        fetches, prev = 0, None
        for point in np.ndindex(*self.grid):
            idx = tuple(int(i) for i in blk.index_map(*point))
            if idx != prev:
                fetches += 1
                prev = idx
        return fetches
