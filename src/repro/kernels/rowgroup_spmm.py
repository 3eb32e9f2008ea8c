"""Row-grouped SpMM: per-group ELL pads à la row-grouped CSR.

The row-grouped-CSR line of work (Oberhuber et al., arXiv:1012.2270;
Heller & Oberhuber, arXiv:1203.5737) attacks row-split's Type 2 waste —
every row padded to the *global* max row length — by grouping rows of
similar length and padding each group only to its own max.  Here rows are
bucketed by the power-of-two octave of their length, each bucket becomes
one ELL structure padded to that bucket's (tile-rounded) max, and the
existing row-split kernel executes each group; a final static row gather
undoes the grouping permutation.  Padding FLOPs drop from
``m * max_len`` to ``sum_g m_g * max_len_g``.

This module is also the registry's extensibility proof: it is wired into
``spmm(method="rowgroup")``, plans, the engine cache, ``python -m
repro.tune`` and ``bench_corpus`` purely through the ``MethodSpec``
registration at the bottom — zero edits to any dispatch site.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import ops as _ops
from . import registry as _registry
from .merge_spmm import default_t
from .rowsplit_spmm import DEFAULT_TL, TM, ell_slots

# Bucketing memo keyed on the live row_ptr object (the pattern_fingerprint
# idiom): one plan request touches group_rows from resolve_params, the
# structure build, and the inline path — the O(m log m) host argsort runs
# once per live pattern per tl instead of once per touch.
_bucket_memo: dict = {}


def group_rows(row_ptr, tl: int):
    """Bucket rows by the octave of their length (host-side, memoized).

    Returns ``(order, groups)``: ``order`` (m,) — row ids sorted by
    bucket, original order preserved within a bucket — and ``groups``, a
    tuple of ``(m_g, l_g)`` pairs (group row count, tile-rounded group
    pad) covering ``order`` contiguously, shortest rows first.
    """
    import weakref

    key = (id(row_ptr), int(tl))
    memo = _bucket_memo.get(key)
    if memo is not None and memo[0]() is row_ptr:
        return memo[1], memo[2]
    lengths = np.diff(np.asarray(row_ptr))
    m = lengths.shape[0]
    if m == 0:
        order, groups = np.zeros(0, np.int64), ()
    else:
        bucket = np.zeros(m, np.int64)
        nz = lengths > 1
        bucket[nz] = np.ceil(np.log2(lengths[nz])).astype(np.int64)
        order = np.argsort(bucket, kind="stable")
        out = []
        start = 0
        for b in np.unique(bucket):
            rows = order[start:start + int((bucket == b).sum())]
            m_g = rows.shape[0]
            max_len = int(lengths[rows].max()) if m_g else 0
            l_g = max(tl, tl * (-(-max(max_len, 1) // tl)))
            out.append((int(m_g), int(l_g)))
            start += m_g
        groups = tuple(out)
    try:
        ref = weakref.ref(row_ptr,
                          lambda _, k=key: _bucket_memo.pop(k, None))
    except TypeError:           # object not weakref-able: skip the memo
        return order, groups
    _bucket_memo[key] = (ref, order, groups)
    return order, groups


def plan_rowgroup_structure(a, *, tl: int = DEFAULT_TL, tm: int = TM,
                            precomputed=None):
    """Pattern-only structure: one ELL block per length bucket.

    Returns a dict with ``groups`` (a tuple of per-group
    ``{cols, slot_nz}`` dicts, each ``(m_g_pad, l_g)`` like the row-split
    structure) and ``inv_pos`` (m,) — the static gather that maps the
    concatenated per-group outputs back to original row order.  Values
    are re-applied per call via ``slot_nz`` (``merge_spmm.apply_vals``).
    ``precomputed``: an ``(order, groups)`` pair from :func:`group_rows`
    the caller already computed for this ``(pattern, tl)``.
    """
    order, groups = precomputed if precomputed is not None \
        else group_rows(a.row_ptr, tl)
    m = a.m
    out_groups = []
    start = 0
    for m_g, l_g in groups:
        rows = jnp.asarray(order[start:start + m_g], jnp.int32)
        start += m_g
        out_groups.append(ell_slots(a, rows, l_g, tm=tm))
    inv = np.zeros(m, np.int64)
    inv[order] = np.arange(m)
    return dict(groups=tuple(out_groups),
                inv_pos=jnp.asarray(inv, jnp.int32))


def rowgroup_execute_parts(groups_meta: tuple, tl: int, fwd: dict,
                           vals: jax.Array, b: jax.Array, *,
                           tk=None, interpret=None, impl="pallas",
                           epilogue=None, bias=None, residual=None,
                           acc_dtype=None, out_dtype=None):
    """Run the row-split kernel once per group, then un-permute rows.

    ``groups_meta`` is the static ``((m_g, l_g), ...)`` tuple (from
    ``PlanMeta.extra``); ``b (..., k, n) -> (..., m, n)`` with leading
    batch dims handled natively by the per-group executes.

    The ``epilogue``'s bias/activation/scale fuse into the per-group
    kernels (the bias rides permuted into group row order and sliced per
    group); a flagged ``residual`` is indexed in *original* row order, so
    it lands after the un-permuting gather — correct because it is the
    last epilogue term, and the groups then flush in ``acc_dtype`` with
    the single ``out_dtype`` cast deferred past the add.
    """
    ep = epilogue
    adt = jnp.float32 if acc_dtype is None else jnp.dtype(acc_dtype)
    odt = jnp.promote_types(vals.dtype, b.dtype) if out_dtype is None \
        else jnp.dtype(out_dtype)
    group_ep, group_out, bias_perm = None, out_dtype, None
    if ep is not None:
        group_ep = dataclasses.replace(ep, residual=False)
        if group_ep.is_identity():
            group_ep = None
        if ep.residual:
            group_out = adt
        if ep.bias:
            m = fwd["inv_pos"].shape[0]
            bias_perm = jnp.zeros((m,), bias.dtype) \
                .at[fwd["inv_pos"]].set(bias)
    outs = []
    start = 0
    for (m_g, _), gs in zip(groups_meta, fwd["groups"]):
        gb = None if bias_perm is None else bias_perm[start:start + m_g]
        start += m_g
        outs.append(_ops.rowsplit_execute(
            gs, vals, b, m=m_g, tk=tk, interpret=interpret,
            impl=impl, epilogue=group_ep, bias=gb, acc_dtype=acc_dtype,
            out_dtype=group_out))
    if not outs:
        return jnp.zeros(b.shape[:-2] + (0, b.shape[-1]), odt)
    out = jnp.concatenate(outs, axis=-2) if len(outs) > 1 else outs[0]
    out = jnp.take(out, fwd["inv_pos"], axis=-2)
    if ep is not None and ep.residual:
        out = (out + residual.astype(out.dtype)).astype(odt)
    return out


# ----------------------------------------------------- static launch model ---


def launch_models(plan, n, batch, var, tk):
    """Static model of the per-group row-split launches.

    One row-split launch per length bucket.  The residual never fuses
    into the groups (it applies after the un-grouping gather) and a
    flagged residual forces the groups to flush in acc precision
    (``rowgroup_execute_parts`` defers the single out cast past the
    add).
    """
    from .rowsplit_spmm import ell_launch
    ep = var.epilogue
    residual = ep is not None and ep.residual
    odt = var.acc_dtype if residual else (var.out_dtype or var.b_dtype)
    models = []
    for g, gs in enumerate(plan.fwd["groups"]):
        models.append(ell_launch(
            f"rowgroup[g{g}]", plan.meta, tuple(gs["slot_nz"].shape),
            n, batch, var, tk,
            with_bias=ep is not None and ep.bias,
            with_residual=False, out_dtype=odt))
    return models


# --------------------------------------------------- MethodSpec adapters ---


def _reject_l_pad(l_pad) -> None:
    if l_pad is not None:
        raise ValueError(
            "method='rowgroup' derives a pad per row group from the "
            "pattern; a global l_pad override is not supported (use "
            "method='rowsplit' for a single explicit pad).")


def _resolve(a, *, t, tl, l_pad):
    t = default_t(a.m, a.nnz_pad) if t is None else t
    tl = DEFAULT_TL if tl is None else tl
    _reject_l_pad(l_pad)
    _, groups = group_rows(a.row_ptr, tl)
    return t, tl, None, groups


def _build_structure(a, meta):
    return plan_rowgroup_structure(a, tl=meta.tl)


def _execute(meta, fwd, vals, b, *, tk, interpret, impl, epilogue=None,
             bias=None, residual=None, acc_dtype=None, out_dtype=None):
    return rowgroup_execute_parts(meta.extra, meta.tl, fwd, vals, b, tk=tk,
                                  interpret=interpret, impl=impl,
                                  epilogue=epilogue, bias=bias,
                                  residual=residual, acc_dtype=acc_dtype,
                                  out_dtype=out_dtype)


def _inline(a, b, *, t, tl, l_pad, extra, tk, interpret, impl):
    if isinstance(a.row_ptr, jax.core.Tracer) or \
            isinstance(a.col_ind, jax.core.Tracer):
        raise ValueError(
            "rowgroup's length bucketing is a host-side decision and "
            "cannot run on a traced CSR. Build an SpmmPlan outside jit "
            "(repro.engine.get_plan) and pass it through the jitted "
            "function.")
    _reject_l_pad(l_pad)
    tl = DEFAULT_TL if tl is None else tl
    # `extra` (group sizes only — it must stay small and hashable for
    # PlanMeta) cannot carry the row `order` the structure needs, but
    # group_rows is memoized per live pattern, so this re-derivation is
    # an O(1) lookup whenever the caller already resolved the policy.
    order, groups = group_rows(a.row_ptr, tl)
    fwd = plan_rowgroup_structure(a, tl=tl, precomputed=(order, groups))
    return rowgroup_execute_parts(groups, tl, fwd, a.vals, b, tk=tk,
                                  interpret=interpret, impl=impl)


_registry.register_method(_registry.MethodSpec(
    name="rowgroup",
    description="row-grouped ELL (arXiv:1012.2270): rows bucketed by "
                "length octave, each group padded to its own max",
    build_structure=_build_structure,
    execute=_execute,
    inline=_inline,
    resolve_params=_resolve,
    tune_candidates=lambda a, wide: [dict()],
    heuristic_rank=None,          # opt-in: explicit method= or TuneDB hits
    traffic=launch_models,
))
