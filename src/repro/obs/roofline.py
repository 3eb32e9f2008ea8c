"""Compulsory-traffic models of SpMM: the bytes each operand and result
moves across HBM at least once.

* :func:`spmm_min_bytes` / :func:`plan_min_bytes` — one forward call
  (moved here from ``benchmarks/roofline.py``, which re-exports it),
* :func:`plan_bwd_min_bytes` / :func:`sddmm_min_bytes` — the same model
  for the custom-VJP backward (transpose-merge dB + SDDMM dvals), the
  floor the static traffic analyzer (``repro.analysis.traffic``) holds
  the backward programs against,
* :func:`epilogue_tail_bytes` / :func:`fused_epilogue_ceiling` — what a
  separate epilogue pass would add, and what fusing it saves.

Device-time roofline shares are the benchmark's (``bench/work.py``, the
published peaks in ``bench/peaks.json``), read from a chip trace.
"""
from __future__ import annotations

# ------------------------------------------------------ bytes/flops model ---


def spmm_min_bytes(m: int, k: int, n: int, nnz: int, *, val_bytes: int = 4,
                   idx_bytes: int = 4, out_bytes: int = 4) -> int:
    """Compulsory traffic of one CSR SpMM: vals + col indices once, the
    dense B panel once, the output C once."""
    return (nnz * (val_bytes + idx_bytes) + k * n * val_bytes
            + m * n * out_bytes)


def epilogue_tail_bytes(m: int, n: int, *, out_bytes: int = 4,
                        bias: bool = False, residual: bool = False) -> int:
    """Traffic of a *separate* elementwise tail program: read C, read the
    epilogue operands, write the result."""
    extra = (m * out_bytes if bias else 0) + \
        (m * n * out_bytes if residual else 0)
    return 2 * m * n * out_bytes + extra


def fused_epilogue_ceiling(m: int, k: int, n: int, nnz: int, *,
                           val_bytes: int = 4, out_bytes: int = 4,
                           bias: bool = True,
                           residual: bool = False) -> float:
    """Bytes-moved speedup ceiling of fusing the tail into the SpMM."""
    spmm = spmm_min_bytes(m, k, n, nnz, val_bytes=val_bytes,
                          out_bytes=out_bytes)
    tail = epilogue_tail_bytes(m, n, out_bytes=out_bytes, bias=bias,
                               residual=residual)
    fused_extra = (m * out_bytes if bias else 0) + \
        (m * n * out_bytes if residual else 0)
    return (spmm + tail) / (spmm + fused_extra)


_DTYPE_BYTES = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2}


def _dtype_bytes(name: str | None) -> int:
    return _DTYPE_BYTES.get(str(name), 4)


def plan_min_bytes(meta, n: int, *, val_dtype: str = "float32",
                   out_dtype: str | None = None, batch: int = 1,
                   epilogue=None, b_dtype: str | None = None) -> int:
    """Compulsory bytes of executing a plan against an n-column B.

    ``meta`` is a ``core.plan.PlanMeta`` or ``distributed.spmm.
    ShardedMeta`` — both carry ``shape`` and ``nnz_pad`` (the static
    nonzero capacity the kernels actually stream, padding included).
    ``batch`` scales the dense legs (B, C, a flagged residual);
    ``b_dtype`` widens/narrows the B leg independently of the values
    (defaults to ``val_dtype``); a fused ``epilogue`` adds its operand
    reads (bias once, residual per batch).  The old
    ``plan_min_bytes(meta, n, val_dtype=..., out_dtype=...)`` spelling
    is unchanged.
    """
    m, k = meta.shape
    vb = _dtype_bytes(val_dtype)
    bb = _dtype_bytes(b_dtype or val_dtype)
    ob = _dtype_bytes(out_dtype or val_dtype)
    total = (meta.nnz_pad * (vb + 4) + batch * k * n * bb
             + batch * m * n * ob)
    if epilogue is not None:
        if getattr(epilogue, "bias", False):
            total += m * bb
        if getattr(epilogue, "residual", False):
            total += batch * m * n * bb
    return total


def sddmm_min_bytes(nnz: int, m: int, k: int, n: int, *, batch: int = 1,
                    dc_dtype: str = "float32",
                    b_dtype: str = "float32") -> int:
    """Compulsory traffic of the SDDMM values-cotangent pass: read the
    output cotangent and B once, the nonzero coordinate streams once,
    write one f32 value per nonzero (``kernels.sddmm``)."""
    dcb = _dtype_bytes(dc_dtype)
    bb = _dtype_bytes(b_dtype)
    return (batch * m * n * dcb + batch * k * n * bb
            + nnz * (4 + 4) + nnz * 4)


def plan_bwd_min_bytes(meta, n: int, *, val_dtype: str = "float32",
                       b_dtype: str | None = None,
                       batch: int = 1) -> int:
    """Compulsory *extra* bytes of the custom-VJP backward, on top of
    the forward: the transpose-merge dB pass (stream the transposed
    structure and values, read the f32 output cotangent, write dB in
    B's dtype) plus the SDDMM dvals pass (:func:`sddmm_min_bytes`).
    The static traffic analyzer holds the traced fwd+bwd program
    against ``plan_min_bytes + plan_bwd_min_bytes``.
    """
    m, k = meta.shape
    vb = _dtype_bytes(val_dtype)
    bb = _dtype_bytes(b_dtype or val_dtype)
    db = (meta.nnz_pad * (vb + 4) + batch * m * n * 4
          + batch * k * n * bb)
    return db + sddmm_min_bytes(meta.nnz_pad, m, k, n, batch=batch,
                                b_dtype=b_dtype or val_dtype)


def spmm_flops(nnz: int, n: int) -> float:
    """Useful flops of one SpMM: a multiply-add per (nonzero, column)."""
    return 2.0 * nnz * n
