"""Pluggable SpMM method registry — adding a method is a registration.

The paper frames SpMM as a *dispatch decision* over a shared CSR input
(merge vs. row-split, §5.4), and the row-grouped-CSR line of work shows
more methods are coming.  Pre-v1 that dispatch was hardwired into if/elif
chains across ``core/spmm.py``, ``core/plan.py``, the engine cache, and
the autotuner; here each method registers one :class:`MethodSpec` bundling
everything those call sites need:

* ``build_structure`` — the pattern-only plan-structure builder,
* ``execute`` — the plan-execute op (Pallas body + XLA ref behind
  ``impl=``), wrapped on demand in a ``custom_vmap`` rule by
  :func:`execute_op`,
* ``inline`` — the plan-per-call form (``spmm(..., plan="inline")``),
* ``resolve_params`` — per-method static-parameter resolution and
  validation (defaults, ``l_pad`` derivation, silent-truncation guards),
* ``tune_candidates`` — the autotuner's static-parameter sweep,
* ``heuristic_rank`` — the analytic cost hook behind ``method="auto"``
  (``None``: opt-in only, never auto-selected).

``core.spmm._forward``, ``core.config.PlanPolicy.resolve``,
``core.plan.build_plan``, ``engine.PlanCache``, ``tune.tune_pattern`` and
``benchmarks/bench_corpus.py`` all dispatch through this table, so a new
method (see ``rowgroup_spmm.py``) touches only its own module plus a
``register_method`` call.
"""
from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable, Sequence

import jax
import numpy as np

from . import merge_spmm as _merge
from . import ops as _ops
from . import rowsplit_spmm as _rowsplit


@dataclasses.dataclass(frozen=True)
class MethodSpec:
    """Everything the engine needs to plan, execute, tune one method.

    Callable contracts (``meta`` is a ``core.plan.PlanMeta``; ``fwd`` the
    method's pattern structure dict; ``a`` a concrete ``CSR``):

    * ``build_structure(a, meta) -> dict`` of static-shaped device arrays
      (pattern-only; values re-applied per call through ``slot_nz``).
    * ``execute(meta, fwd, vals, b, *, tk, interpret, impl, epilogue=None,
      bias=None, residual=None, acc_dtype=None, out_dtype=None) -> C``
      with ``b (..., k, n) -> (..., m, n)`` (leading batch dims native).
      ``epilogue`` is a ``core.Epilogue`` fused into the output write
      (``bias (m,)``; ``residual (..., m, n)``, broadcast over the batch);
      ``acc_dtype``/``out_dtype`` set accumulation and output precision.
    * ``inline(a, b, *, t, tl, l_pad, extra, tk, interpret, impl) -> C``
      — the plan-per-call regime (``t``/``tl``/``l_pad`` may be None:
      kernel defaults; ``extra`` is the already-resolved
      ``PlanMeta.extra`` when the caller ran ``resolve_params``, else
      None — a hint methods may use to skip derivable work); ``None`` if
      the method has no inline form.
    * ``resolve_params(a, *, t, tl, l_pad) -> (t, tl, l_pad, extra)``:
      fill defaults, validate, and compute ``extra`` (a hashable tuple of
      method-specific statics stored in ``PlanMeta.extra``).
    * ``tune_candidates(a, wide) -> [ {t=...} | {l_pad=...} | {} , ...]``
      — kwargs for ``build_plan`` sweeps in ``repro.tune``.
    * ``heuristic_rank(a, heuristic) -> float`` — analytic cost; the
      lowest-ranked method wins ``method="auto"`` (ties go to the
      later-registered spec, preserving the paper rule's ``d >=
      threshold -> rowsplit``).
    * ``traffic(plan, n, batch, var, tk) -> [KernelLaunch]`` — the
      static launch model(s) of the method's ``impl="pallas"`` lowering
      (``repro.kernels.introspect``), consumed by the kernel audit, the
      coalescing checker and the bytes-moved analyzer
      (``repro.analysis``).  ``None`` strands the method outside the
      static-analysis gate and is itself a diagnostic (K001/T101) —
      coverage is bidirectionally loud, never silently skipped.
    """

    name: str
    description: str
    build_structure: Callable
    execute: Callable
    inline: Callable | None
    resolve_params: Callable
    tune_candidates: Callable
    heuristic_rank: Callable | None
    traffic: Callable | None = None


_REGISTRY: dict[str, MethodSpec] = {}


def register_method(spec: MethodSpec, *, override: bool = False) -> None:
    """Register an SpMM method. Raises on duplicate names unless
    ``override`` (tests may swap in instrumented specs)."""
    if spec.name in _REGISTRY and not override:
        raise ValueError(f"SpMM method {spec.name!r} is already registered "
                         "(pass override=True to replace it)")
    _REGISTRY[spec.name] = spec


def method_names() -> tuple[str, ...]:
    """Registered method names, in registration order."""
    return tuple(_REGISTRY)


def get_method(name: str) -> MethodSpec:
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(
            f"unknown SpMM method: {name!r}; registered methods: "
            + ", ".join(sorted(_REGISTRY)))
    return spec


def choose_auto(a, heuristic) -> str:
    """Resolve ``method="auto"`` through the registered cost hooks.

    Ties go to the later-registered spec, so with only the built-in pair
    this reproduces ``Heuristic.choose`` exactly (``d < threshold ->
    merge``, else rowsplit).
    """
    best = None
    for name, spec in _REGISTRY.items():
        if spec.heuristic_rank is None:
            continue
        rank = spec.heuristic_rank(a, heuristic)
        if best is None or rank <= best[0]:
            best = (rank, name)
    if best is None:
        raise ValueError("no registered SpMM method is heuristic-eligible")
    return best[1]


# Bounded like the per-method op caches it replaced: keys embed the full
# static PlanMeta, so a long-lived server cycling patterns cannot grow it
# without bound; entries are pure functions of the key.
@functools.lru_cache(maxsize=512)
def execute_op(meta, tk: int | None, interpret: bool | None, impl: str,
               epilogue=None, acc_dtype: str | None = None,
               out_dtype: str | None = None):
    """A method's ``execute`` wrapped with the explicit vmap rule.

    The ``custom_vmap`` wrapper rewrites a vmapped dense-operand axis onto
    the method's native leading-batch path (a flagged ``residual`` batches
    with it; ``bias`` stays unbatched — JAX sums its cotangent across the
    vmap axis); anything else falls back to a sequential ``lax.map``.
    Only for use where JAX vmaps but never differentiates (the custom-VJP
    fwd/bwd bodies in ``core.spmm``).  ``bias``/``residual`` are always
    positional operands of the wrapped op (pass None when the epilogue
    doesn't flag them) so one call shape serves every epilogue.
    """
    spec = get_method(meta.method)

    def fn(fwd, vals, b, bias, residual):
        return spec.execute(meta, fwd, vals, b, tk=tk, interpret=interpret,
                            impl=impl, epilogue=epilogue, bias=bias,
                            residual=residual, acc_dtype=acc_dtype,
                            out_dtype=out_dtype)

    def native(in_batched):
        fwd_b, vals_b, b_b, bias_b, res_b = in_batched
        res_leaves = jax.tree.leaves(res_b)
        return (b_b and not vals_b and not any(jax.tree.leaves(fwd_b))
                and not any(jax.tree.leaves(bias_b))
                and (not res_leaves or all(res_leaves)))

    return _ops._vmappable(fn, native)


# ------------------------------------------------------ built-in methods ---


def _max_row_len(a) -> int:
    lengths = np.diff(np.asarray(a.row_ptr))
    return int(lengths.max()) if lengths.size else 0


def _merge_resolve(a, *, t, tl, l_pad):
    t = _merge.default_t(a.m, a.nnz_pad) if t is None else t
    tl = _rowsplit.DEFAULT_TL if tl is None else tl
    return t, tl, None, ()          # merge has no row pad


def _merge_execute(meta, fwd, vals, b, *, tk, interpret, impl,
                   epilogue=None, bias=None, residual=None,
                   acc_dtype=None, out_dtype=None):
    return _ops.merge_execute(fwd, vals, b, m=meta.m, tk=tk,
                              interpret=interpret, impl=impl,
                              epilogue=epilogue, bias=bias,
                              residual=residual, acc_dtype=acc_dtype,
                              out_dtype=out_dtype)


def _merge_candidates(a, wide: bool) -> Sequence[dict]:
    t = _merge.default_t(a.m, a.nnz_pad)
    cands = [dict(t=t)]
    if wide:
        cands += [dict(t=c) for c in (t // 2, 2 * t)
                  if 8 <= c <= _merge.T_MAX]
    return cands


def _merge_inline(a, b, *, t, tl, l_pad, extra, tk, interpret, impl):
    return _ops.merge_spmm(a, b, t=t, tk=tk, interpret=interpret, impl=impl)


def _rowsplit_resolve(a, *, t, tl, l_pad):
    t = _merge.default_t(a.m, a.nnz_pad) if t is None else t
    tl = _rowsplit.DEFAULT_TL if tl is None else tl
    max_len = _max_row_len(a)
    if l_pad is None:
        l_pad = max(max_len, 1)
    elif l_pad < max_len:
        # An undersized pad would make plan_rowsplit_structure's ELL mask
        # silently truncate long rows — wrong C, no error.  The pattern is
        # concrete here, so validate at the single choke point every plan
        # request (user kwargs, TuneDB replays, the engine cache) funnels
        # through.
        raise ValueError(
            f"l_pad={l_pad} is smaller than the pattern's longest row "
            f"({max_len} nonzeroes): the row-split ELL layout would "
            "silently drop nonzeroes and return a wrong C. Pass "
            f"l_pad >= {max_len}, or omit l_pad to derive it from the "
            "pattern.")
    return t, tl, l_pad, ()


def _rowsplit_structure(a, meta):
    return dict(_rowsplit.plan_rowsplit_structure(a, l_pad=meta.l_pad,
                                                  tl=meta.tl))


def _rowsplit_execute(meta, fwd, vals, b, *, tk, interpret, impl,
                      epilogue=None, bias=None, residual=None,
                      acc_dtype=None, out_dtype=None):
    return _ops.rowsplit_execute(fwd, vals, b, m=meta.m, tk=tk,
                                 interpret=interpret, impl=impl,
                                 epilogue=epilogue, bias=bias,
                                 residual=residual, acc_dtype=acc_dtype,
                                 out_dtype=out_dtype)


def _rowsplit_candidates(a, wide: bool) -> Sequence[dict]:
    lmax = max(_max_row_len(a), 1)
    cands = [dict(l_pad=lmax)]
    if wide:
        up8 = -(-lmax // 8) * 8
        if up8 != lmax:
            cands.append(dict(l_pad=up8))    # tile-aligned ELL rows
    return cands


def _rowsplit_inline(a, b, *, t, tl, l_pad, extra, tk, interpret, impl):
    tl = _rowsplit.DEFAULT_TL if tl is None else tl
    return _ops.rowsplit_spmm(a, b, l_pad=l_pad, tl=tl, tk=tk,
                              interpret=interpret, impl=impl)


register_method(MethodSpec(
    name="merge",
    description="merge-based nonzero splitting (paper §4.2): equal "
                "nonzeroes per chunk, broken at output row tiles",
    build_structure=lambda a, meta: dict(
        _merge.plan_merge_structure(a, t=meta.t)),
    execute=_merge_execute,
    inline=_merge_inline,
    resolve_params=_merge_resolve,
    tune_candidates=_merge_candidates,
    # The paper's §5.4 rule as a cost: d below the threshold prefers merge.
    heuristic_rank=lambda a, h: h.mean_row_length(a) - h.threshold,
    traffic=_merge.launch_models,
))

register_method(MethodSpec(
    name="rowsplit",
    description="row splitting (paper §4.1): one ELL-padded row tile per "
                "grid step",
    build_structure=_rowsplit_structure,
    execute=_rowsplit_execute,
    inline=_rowsplit_inline,
    resolve_params=_rowsplit_resolve,
    tune_candidates=_rowsplit_candidates,
    heuristic_rank=lambda a, h: h.threshold - h.mean_row_length(a),
    traffic=_rowsplit.launch_models,
))
