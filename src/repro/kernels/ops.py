"""Jitted public wrappers for the Pallas kernels.

Every op handles padding to tile multiples, backend selection (interpret
mode on CPU — the kernel body runs in Python for bit-level validation
against ref.py; compiled Mosaic on real TPUs), and exposes an XLA fallback
(``impl="xla"``) built from the same dataflow for A/B benchmarking.

The plan-execute ops (``merge_execute``/``rowsplit_execute``/``sddmm``)
accept dense operands with arbitrary leading batch dims — ``b (..., k, n)``
folds into the kernels' leading batch grid axis, one dispatch for the whole
stack.  The forward's vmap wrapping is generic now — the method registry's
``registry.execute_op`` wraps any registered method's execute in an
explicit ``jax.custom_batching.custom_vmap`` rule (vmapped batch axis →
native stacked axis instead of tracing into ``pallas_call``); this module
keeps only the wrapped ops the custom-VJP *backward* body needs
(``merge_execute_op`` for the transpose dB plan, ``sddmm_op`` for the
values cotangent).  The raw ops stay plain so forward-only XLA callers
keep ordinary autodiff.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import CSR
from repro.core.epilogue import apply_epilogue
from repro.obs import scope
from . import flash_attention as _flash
from . import merge_spmm as _merge
from . import moe_gemm as _moe
from . import ref as _ref
from . import rowsplit_spmm as _rowsplit
from . import sddmm as _sddmm


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _lead_fold(x):
    """Fold leading batch dims of (..., r, n) into one axis: (nb, r, n)."""
    return x.reshape((-1,) + x.shape[-2:])


@functools.partial(jax.jit, static_argnames=("t", "tk", "interpret", "impl"))
def merge_spmm(a: CSR, b: jax.Array, *, t: int | None = None,
               tk: int | None = None, interpret: bool | None = None,
               impl: str = "pallas"):
    """Merge-based SpMM: C = A @ B with equal-nonzero load balancing."""
    t = _merge.default_t(a.m, a.nnz_pad) if t is None else t
    if impl == "xla":
        return _ref.spmm_merge_ref(a, b, t=t)
    if interpret is None:
        interpret = _interpret_default()
    m = a.m
    with scope("pad"):
        b2 = _pad_axis(b, _merge.TN, 1)
    structure = _merge.plan_merge_structure(a, t=t)
    m_pad = _merge.TM * (-(-m // _merge.TM))
    out = _merge.merge_spmm_pallas(structure, a.vals, b2[None], m_pad,
                                   tk=tk, interpret=interpret)
    with scope("unpad"):
        return out[0, :m, : b.shape[1]]


def rowsplit_spmm(a: CSR, b: jax.Array, *, l_pad: int | None = None,
                  tl: int = _rowsplit.DEFAULT_TL, tk: int | None = None,
                  interpret: bool | None = None, impl: str = "pallas"):
    """Row-split SpMM: C = A @ B, one row tile per grid step (ELL-padded).

    ``l_pad``: static max row length.  Outside jit it is derived from the
    concrete row_ptr; under tracing it must be supplied.  A supplied
    ``l_pad`` smaller than the true max row length would silently truncate
    rows, so it is validated whenever the pattern is concrete.
    """
    traced = isinstance(a.row_ptr, jax.core.Tracer)
    max_len = None
    if not traced:
        lengths = np.diff(np.asarray(a.row_ptr))
        max_len = int(lengths.max()) if lengths.size else 0
    if l_pad is None:
        if traced:
            raise ValueError(
                "rowsplit_spmm under trace requires a static l_pad (the max "
                "row length is data-dependent and cannot be derived from a "
                "traced row_ptr). Either pass l_pad= explicitly, or build an "
                "SpmmPlan outside jit — repro.engine.get_plan(a) / "
                "repro.core.plan.build_plan(a) — which captures the static "
                "l_pad once per sparsity pattern and can be passed through "
                "jitted code freely.")
        l_pad = max(max_len, 1)
    elif max_len is not None and l_pad < max_len:
        raise ValueError(
            f"l_pad={l_pad} is smaller than the pattern's longest row "
            f"({max_len} nonzeroes): the ELL layout would silently drop "
            f"nonzeroes and return a wrong C. Pass l_pad >= {max_len}, or "
            "omit l_pad to derive it from the pattern.")
    return _rowsplit_spmm_jit(a, b, l_pad=l_pad, tl=tl, tk=tk,
                              interpret=interpret, impl=impl)


@functools.partial(jax.jit,
                   static_argnames=("l_pad", "tl", "tk", "interpret", "impl"))
def _rowsplit_spmm_jit(a: CSR, b: jax.Array, *, l_pad: int,
                       tl: int = _rowsplit.DEFAULT_TL, tk: int | None = None,
                       interpret: bool | None = None, impl: str = "pallas"):
    if impl == "xla":
        return _ref.spmm_rowsplit_ref(a, b, tl=tl, l_pad=l_pad)
    if interpret is None:
        interpret = _interpret_default()
    with scope("pad"):
        b2 = _pad_axis(b, _rowsplit.TN, 1)
    structure = _rowsplit.plan_rowsplit_structure(a, l_pad=l_pad, tl=tl)
    out = _rowsplit.rowsplit_spmm_pallas(structure, a.vals, b2[None], tk=tk,
                                         interpret=interpret)
    with scope("unpad"):
        return out[0, : a.m, : b.shape[1]]


def _resolve_dtypes(vals, b, acc_dtype, out_dtype):
    """(acc, out) dtypes: f32 accumulation and operand promotion defaults."""
    adt = jnp.float32 if acc_dtype is None else jnp.dtype(acc_dtype)
    odt = jnp.promote_types(vals.dtype, b.dtype) if out_dtype is None \
        else jnp.dtype(out_dtype)
    return adt, odt


def _apply_tail(c, ep, bias, residual):
    """Post-hoc epilogue for the degenerate (kernel-free) early-outs: even
    with no contributing nonzero, ``act(0 + bias) * scale + residual`` is
    generally nonzero and must still be produced."""
    if ep is None:
        return c
    with scope("unpad"):
        bias_col = bias.astype(c.dtype)[:, None] if ep.bias else None
        return apply_epilogue(c, ep, bias_col,
                              residual if ep.residual else None)


def _pad_epilogue_operands(ep, bias, residual, lead, m, n, m_pad, tn):
    """Kernel-shaped epilogue operands: bias (m,) → (m_pad,); residual
    broadcast over ``lead`` then folded/padded like the dense operand."""
    extra = {}
    if ep is None:
        return extra
    extra["epilogue"] = ep
    with scope("pad"):
        if ep.bias:
            extra["bias"] = jnp.pad(bias, (0, m_pad - m))
        if ep.residual:
            res3 = _lead_fold(jnp.broadcast_to(residual, lead + (m, n)))
            res3 = jnp.pad(res3, ((0, 0), (0, m_pad - m), (0, 0)))
            extra["residual"] = _pad_axis(res3, tn, 2)
    return extra


@functools.partial(jax.jit,
                   static_argnames=("m", "tk", "interpret", "impl",
                                    "epilogue", "acc_dtype", "out_dtype"))
def merge_execute(structure: dict, vals: jax.Array, b: jax.Array, *, m: int,
                  tk: int | None = None, interpret: bool | None = None,
                  impl: str = "pallas", epilogue=None, bias=None,
                  residual=None, acc_dtype=None, out_dtype=None):
    """Execute a prebuilt merge structure: C = A @ B with per-call values.

    ``structure`` is the pattern-only plan from
    ``merge_spmm.plan_merge_structure`` (built once per sparsity pattern by
    ``repro.core.plan`` / cached by ``repro.engine``); ``vals`` is the
    (nnz_pad,) value vector of the call, laid out per chunk through
    ``slot_nz`` (one XLA gather per call; the kernel reads each chunk's
    values as SMEM scalars).  ``b``
    may carry leading batch dims: (..., k, n) → (..., m, n), one kernel
    dispatch overall.

    ``epilogue`` (``repro.core.Epilogue``) fuses ``act(C + bias) * scale +
    residual`` into the accumulator flush; ``bias (m,)`` and ``residual
    (..., m, n)`` (broadcast over the batch) ride per its flags.
    ``acc_dtype`` (default f32) is the accumulation precision, ``out_dtype``
    (default: operand promotion) the single C write.
    """
    lead, n = b.shape[:-2], b.shape[-1]
    adt, odt = _resolve_dtypes(vals, b, acc_dtype, out_dtype)
    ep = epilogue
    if m == 0 or b.shape[-2] == 0:
        # Degenerate 0-row / 0-col pattern: no nonzero contributes — skip
        # the kernel, but the epilogue tail still applies to C = 0.
        c = jnp.zeros(lead + (m, n), adt)
        return _apply_tail(c, ep, bias, residual).astype(odt)
    if impl == "xla":
        with scope("kernel"):
            res = None if ep is None or not ep.residual else \
                jnp.broadcast_to(residual, lead + (m, n))
            return _ref.merge_execute_ref(
                structure, vals, b, m, _merge.TM, epilogue=ep, bias=bias,
                residual=res, acc_dtype=adt, out_dtype=odt)
    if interpret is None:
        interpret = _interpret_default()
    with scope("pad"):
        b3 = _pad_axis(_lead_fold(b), _merge.TN, 2)
    m_pad = _merge.TM * (-(-m // _merge.TM))
    extra = _pad_epilogue_operands(ep, bias, residual, lead, m, n, m_pad,
                                   _merge.TN)
    out = _merge.merge_spmm_pallas(structure, vals, b3, m_pad, tk=tk,
                                   interpret=interpret, acc_dtype=adt,
                                   out_dtype=odt, **extra)
    with scope("unpad"):
        return out[:, :m, :n].reshape(lead + (m, n))


@functools.partial(jax.jit,
                   static_argnames=("m", "tk", "interpret", "impl",
                                    "epilogue", "acc_dtype", "out_dtype"))
def rowsplit_execute(structure: dict, vals: jax.Array, b: jax.Array, *,
                     m: int, tk: int | None = None,
                     interpret: bool | None = None,
                     impl: str = "pallas", epilogue=None, bias=None,
                     residual=None, acc_dtype=None, out_dtype=None):
    """Execute a prebuilt ELL structure: row-split SpMM with per-call values.

    The static ``l_pad`` is baked into the structure's (m_pad, L) shape, so
    this is trace-safe with no l_pad argument.  ``b`` may carry leading
    batch dims: (..., k, n) → (..., m, n).  ``epilogue``/``bias``/
    ``residual`` and ``acc_dtype``/``out_dtype`` as in ``merge_execute``.
    """
    lead, n = b.shape[:-2], b.shape[-1]
    adt, odt = _resolve_dtypes(vals, b, acc_dtype, out_dtype)
    ep = epilogue
    if m == 0 or b.shape[-2] == 0:
        c = jnp.zeros(lead + (m, n), adt)
        return _apply_tail(c, ep, bias, residual).astype(odt)
    if impl == "xla":
        with scope("kernel"):
            res = None if ep is None or not ep.residual else \
                jnp.broadcast_to(residual, lead + (m, n))
            return _ref.rowsplit_execute_ref(
                structure, vals, b, m, epilogue=ep, bias=bias, residual=res,
                acc_dtype=adt, out_dtype=odt)
    if interpret is None:
        interpret = _interpret_default()
    with scope("pad"):
        b3 = _pad_axis(_lead_fold(b), _rowsplit.TN, 2)
    m_pad = structure["cols"].shape[0]
    extra = _pad_epilogue_operands(ep, bias, residual, lead, m, n, m_pad,
                                   _rowsplit.TN)
    out = _rowsplit.rowsplit_spmm_pallas(structure, vals, b3, tk=tk,
                                         interpret=interpret, acc_dtype=adt,
                                         out_dtype=odt, **extra)
    with scope("unpad"):
        return out[:, :m, :n].reshape(lead + (m, n))


@functools.partial(jax.jit, static_argnames=("interpret", "impl"))
def sddmm(rows: jax.Array, cols: jax.Array, valid: jax.Array, dc: jax.Array,
          b: jax.Array, *, interpret: bool | None = None,
          impl: str = "pallas"):
    """Sampled dense-dense matmul over a pattern: dvals[p] = dC[r_p]·B[c_p].

    ``rows``/``cols`` are per-nonzero coordinates (in-bounds everywhere;
    padded entries masked off by ``valid``).  This is the values-cotangent
    kernel of the differentiable SpMM.  ``dc``/``b`` may carry matching
    leading batch dims, kept per element: (..., m, n) × (..., k, n) →
    (..., nnz_pad); shared-values callers reduce the leading dims.
    """
    lead = dc.shape[:-2]
    nnz_pad = rows.shape[0]
    if nnz_pad == 0 or dc.shape[-2] == 0 or b.shape[-2] == 0:
        # 0-nnz / 0-row / 0-col patterns: every slot is padding — the
        # cotangent is identically zero (and the kernel's (p, tq) chunking
        # has nothing to chunk).
        return jnp.zeros(lead + (nnz_pad,), dc.dtype)
    if impl == "xla":
        with scope("kernel"):
            return _ref.sddmm_ref(rows, cols, valid, dc, b)
    if interpret is None:
        interpret = _interpret_default()
    tq = _sddmm.TQ
    p = -(-nnz_pad // tq)
    with scope("pad"):
        rows2 = _pad_axis(rows, tq, 0).reshape(p, tq)
        cols2 = _pad_axis(cols, tq, 0).reshape(p, tq)
        dc3 = _pad_axis(_lead_fold(dc), _sddmm.TN, 2)
        b3 = _pad_axis(_lead_fold(b), _sddmm.TN, 2)
    out = _sddmm.sddmm_pallas(rows2, cols2, dc3, b3, interpret=interpret)
    with scope("unpad"):
        dvals = out.reshape(out.shape[0], -1)[:, :nnz_pad]
        return jnp.where(valid, dvals.reshape(lead + (nnz_pad,)),
                         0).astype(dc.dtype)


# ---------------------------------------------------- explicit vmap rules ---
#
# ``jax.custom_batching.custom_vmap`` wrappers over the plan-execute ops.
# A vmapped batch axis on the dense operand(s) is rewritten onto the ops'
# native leading-batch path — i.e. into the kernels' batch grid axis — and
# any other batching (per-element values, batched structures) falls back to
# a sequential ``lax.map``, which is always correct.  custom_vmap does not
# compose with reverse-mode autodiff, so these wrapped forms must only be
# used where autodiff never differentiates through them: the forward and
# backward *bodies* of ``repro.core.spmm``'s custom VJP (which JAX vmaps,
# but never differentiates).


def _vmappable(fn, native_when):
    op = jax.custom_batching.custom_vmap(fn)

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        if native_when(in_batched):
            # The batch axis becomes a native leading dim; recursing
            # through ``op`` keeps any remaining outer vmap axes handled.
            return op(*args), True

        def one(i):
            sliced = tuple(
                jax.tree.map(lambda bt, x: x[i] if bt else x, tb, arg)
                for tb, arg in zip(in_batched, args))
            return op(*sliced)

        return jax.lax.map(one, jnp.arange(axis_size)), True

    return op


def _structure_free(tree_batched) -> bool:
    return not any(jax.tree.leaves(tree_batched))


# Bounded: keys embed per-pattern statics (m, k), so an unbounded cache
# would grow with every distinct pattern shape a long-lived server sees.
# Entries are pure functions of the key — eviction just rebuilds the thin
# wrapper; the jitted ops underneath keep their stable identity.
_OP_CACHE_SIZE = 512


@functools.lru_cache(maxsize=_OP_CACHE_SIZE)
def merge_execute_op(m: int, tk: int | None, interpret: bool | None,
                     impl: str):
    """``merge_execute`` with an explicit vmap rule (statics closed over)."""
    fn = lambda structure, vals, b: merge_execute(
        structure, vals, b, m=m, tk=tk, interpret=interpret, impl=impl)

    def native(in_batched):
        st, va, bb = in_batched
        return bb and not va and _structure_free(st)

    return _vmappable(fn, native)


@functools.lru_cache(maxsize=_OP_CACHE_SIZE)
def sddmm_op(interpret: bool | None, impl: str):
    """``sddmm`` with an explicit vmap rule.

    Native when both dense operands batch together (the kernel keeps the
    axis per element, exactly vmap's semantics); coordinate batching falls
    back to the sequential map.
    """
    fn = lambda rows, cols, valid, dc, b: sddmm(
        rows, cols, valid, dc, b, interpret=interpret, impl=impl)

    def native(in_batched):
        rr, cc, vv, dcb, bb = in_batched
        return dcb and bb and not (rr or cc or vv)

    return _vmappable(fn, native)


def moe_group_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   tt: int = _moe.TT, interpret: bool | None = None,
                   impl: str = "pallas"):
    """Grouped GEMM over expert-sorted tokens (merge-based balancing).

    x (tokens_pad, d_in) sorted by expert; w (E, d_in, d_out);
    group_sizes (E,) padded sizes, multiples of ``tt``, summing to
    tokens_pad.
    """
    if interpret is None:
        interpret = _interpret_default()
    tokens, d_in = x.shape
    e, _, d_out = w.shape
    if impl == "xla":
        block_expert = _moe.plan_groups(group_sizes, tokens, tt)
        token_expert = jnp.repeat(block_expert, tt, total_repeat_length=tokens)
        return _ref.moe_group_gemm_ref(x, w, token_expert)
    assert tokens % tt == 0
    x2 = _pad_axis(x, _moe.TDK, 1)
    w2 = _pad_axis(_pad_axis(w, _moe.TDK, 1), _moe.TDN, 2)
    block_expert = _moe.plan_groups(group_sizes, tokens, tt)
    out = _moe.moe_group_gemm_pallas(x2, w2, block_expert, tt=tt,
                                     interpret=interpret)
    return out[:, :d_out]


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def flash_attention(q, k, v, *, bq: int = _flash.DEFAULT_BQ,
                    bk: int = _flash.DEFAULT_BK,
                    interpret: bool | None = None):
    """Causal flash attention via the Pallas kernel.

    q (b, s, h, dh); k/v (b, s, kv, dh) with h % kv == 0 — KV heads are
    broadcast to the query heads (GQA), then (b, h) folds into the grid's
    batch dimension.  Sequence is padded to the block size (padded queries
    are discarded; padded keys sit in the causal future and are masked).
    """
    if interpret is None:
        interpret = _interpret_default()
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    kb = jnp.repeat(k, g, axis=2) if g > 1 else k
    vb = jnp.repeat(v, g, axis=2) if g > 1 else v
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    qf, kf, vf = fold(q), fold(kb), fold(vb)
    pad = (-s) % max(bq, bk)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
    out = _flash.flash_attention_pallas(qf, kf, vf, bq=bq, bk=bk,
                                        interpret=interpret)
    out = out[:, :s]
    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
