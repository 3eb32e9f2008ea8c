"""Public SpMM API (v1): registry-dispatched, plan-once/execute-many,
batched, and differentiable.

    C = spmm(A, B)                            # auto: TuneDB ladder → §5.4
    C = spmm(A, B, PlanPolicy(method="merge"))         # force a method
    C = spmm(A, B, exec=ExecutionConfig(impl="xla"))   # pick the backend

    plan = repro.engine.get_plan(A)           # once per sparsity pattern
    C = spmm(A, B, plan=plan)                 # jit-safe, never replans
    C = execute_plan(plan, A.vals, B)         # the explicit-plan core
    C = execute_plan(plan, A.vals, Bs)        # Bs (batch, k, n): one plan,
                                              # many problems, one dispatch

The two halves of the old kwarg sprawl are split by lifetime:
``PlanPolicy`` (method/t/l_pad/heuristic/tunedb — decided once per
pattern, host-side, hashed into the engine cache key) and
``ExecutionConfig`` (impl/interpret/tk — per call, trace-safe).  The
pre-v1 kwargs survive as deprecation shims for one release.  Method
dispatch — including the inline plan-per-call path — goes through the
method registry (``repro.kernels.registry``), and ``method="auto"``
resolves through one ``PlanPolicy.resolve`` for both the planned and the
inline path, so the two can never pick different kernels for the same
matrix.

With a concrete (non-traced) CSR, ``spmm`` routes through the engine's
plan cache automatically.  Either way execution is differentiable via
``jax.custom_vjp``: ``dB = Aᵀ @ dC`` runs through the plan's cached
transpose (CSC-view) merge plan — equal-nonzero balanced, like the forward
— and ``dvals`` is a sampled-dense-dense (gather-dot) kernel over the
pattern (``repro.kernels.sddmm``).

Batching is first-class in two equivalent forms: pass ``B`` with leading
batch dims (``(..., k, n)``, folded into the kernels' batch grid axis) or
``jax.vmap`` the 2-D call — the custom-VJP's forward/backward bodies call
``custom_vmap``-wrapped ops (``registry.execute_op``), whose explicit vmap
rule rewrites a vmapped axis onto that same native batch path.  Values are
shared across the batch (one frozen pattern, one value vector, many dense
operands — the serving regime), so the batched VJP reduces the
values-cotangent over the batch dims.

Device sharding rides the same dispatch: a ``PlanPolicy`` with ``shards=``
set resolves to a ``repro.distributed.spmm.ShardedSpmmPlan`` — nnz-balanced
row (or column) shards, one local plan per shard — and both ``spmm`` and
``A @ B`` execute it transparently.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.obs import trace as _trace

from .config import (ExecutionConfig, PlanPolicy, _UNSET, coalesce_exec,
                     coalesce_policy)
from .csr import CSR
from .epilogue import Epilogue, activation_fn, apply_epilogue
from .plan import SpmmPlan, PlanMeta

# Per-plan execute counts.  Gated on the tracing flag at the call site:
# execute_plan is the engine's hottest eager entry point and the
# observability contract is zero-cost-when-disabled.
_plan_execute = _obs.registry.counter(
    "plan_execute_total", "execute_plan dispatches by plan and impl",
    labels=("plan", "impl"))


def _plan_label(meta: PlanMeta) -> str:
    m, k = meta.shape
    return f"{meta.method}:{m}x{k}:nnz{meta.nnz_pad}"


def _record_dispatch(meta: PlanMeta, b, exec: ExecutionConfig) -> None:
    # Callers gate on _trace._enabled.
    _plan_execute.labels(plan=_plan_label(meta), impl=exec.impl).inc()
    ep = exec.epilogue
    _trace.event(
        "dispatch", cat="dispatch", method=meta.method, impl=exec.impl,
        m=int(meta.shape[0]), k=int(meta.shape[1]),
        nnz_pad=int(meta.nnz_pad), n=int(b.shape[-1]),
        batch=list(b.shape[:-2]), tk=exec.tk, acc_dtype=exec.acc_dtype,
        out_dtype=exec.out_dtype,
        epilogue=(dict(bias=ep.bias, residual=ep.residual,
                       activation=ep.activation,
                       scale=ep.scale is not None)
                  if ep is not None else None))


def _ops():
    # deferred: repro.kernels imports repro.core.csr at module scope, so an
    # eager import here would be circular
    from repro.kernels import ops
    return ops


def _registry():
    from repro.kernels import registry
    return registry


def _is_traced(a: CSR) -> bool:
    return isinstance(a.row_ptr, jax.core.Tracer) or \
        isinstance(a.col_ind, jax.core.Tracer)


# --------------------------------------------------- plan execution core ---


def _resolve_exec(where: str, m: int, vals, b, exec: ExecutionConfig,
                  bias, residual) -> ExecutionConfig:
    """Normalize the per-call config against the actual operands.

    Resolves the epilogue (auto-derived when ``bias``/``residual`` are
    passed without one; flag/operand mismatches raise), canonicalizes
    ``acc_dtype``/``out_dtype`` against the operand dtypes, and rejects
    non-floating or precision-losing combinations up front — the kernels'
    gathers and accumulators would otherwise return silently-wrong C.
    """
    for name, x in (("vals", vals), ("b", b)):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise TypeError(
                f"{where}() requires floating-point operands; {name} has "
                f"dtype {x.dtype}. Cast explicitly — integer/bool "
                "accumulation is not supported by the kernels.")
    promoted = jnp.promote_types(vals.dtype, b.dtype)
    acc = jnp.dtype(exec.acc_dtype) if exec.acc_dtype is not None \
        else jnp.promote_types(promoted, jnp.float32)
    if jnp.promote_types(promoted, acc) != acc:
        raise ValueError(
            f"acc_dtype={acc.name} cannot hold the promoted operand dtype "
            f"{promoted.name} (vals {vals.dtype}, b {b.dtype}): "
            "accumulating below the input precision silently loses bits. "
            "Use a wider acc_dtype, or cast the operands down explicitly.")
    out = jnp.dtype(exec.out_dtype) if exec.out_dtype is not None \
        else promoted
    ep = exec.epilogue
    if ep is None and (bias is not None or residual is not None):
        ep = Epilogue(bias=bias is not None, residual=residual is not None)
    if ep is not None:
        for flag, operand, name in ((ep.bias, bias, "bias"),
                                    (ep.residual, residual, "residual")):
            if flag and operand is None:
                raise ValueError(
                    f"{where}(): the epilogue flags {name} but no {name}= "
                    "operand was passed.")
            if not flag and operand is not None:
                raise ValueError(
                    f"{where}(): a {name}= operand was passed but the "
                    f"explicit epilogue does not flag {name} — it would "
                    f"be silently ignored. Set Epilogue({name}=True) or "
                    "drop the operand.")
        if ep.bias and bias.shape != (m,):
            raise ValueError(
                f"{where}(): bias must have shape ({m},) — one entry per "
                f"C row — got {bias.shape}.")
        if ep.residual and (residual.ndim < 2
                            or residual.shape[-2:] != (m, b.shape[-1])):
            raise ValueError(
                f"{where}(): residual must have shape (..., {m}, "
                f"{b.shape[-1]}) matching C, got {residual.shape}.")
        if ep.is_identity():
            ep = None
    return dataclasses.replace(exec, epilogue=ep, acc_dtype=acc.name,
                               out_dtype=out.name)


def _forward(meta: PlanMeta, fwd: dict, vals, b, exec: ExecutionConfig,
             bias, residual, *, vmappable: bool):
    registry = _registry()
    # Always on: a named scope is trace-time metadata, free at run time.
    # Its parts (gather_vals, widen_b, pad, kernel, unpad) open inside the
    # kernel wrappers; ``repro.obs.device_scopes`` reads them back.
    with _obs.scope("spmm"):
        if vmappable:
            op = registry.execute_op(meta, exec.tk, exec.interpret,
                                     exec.impl, exec.epilogue,
                                     exec.acc_dtype, exec.out_dtype)
            return op(fwd, vals, b, bias, residual)
        return registry.get_method(meta.method).execute(
            meta, fwd, vals, b, tk=exec.tk, interpret=exec.interpret,
            impl=exec.impl, epilogue=exec.epilogue, bias=bias,
            residual=residual, acc_dtype=exec.acc_dtype,
            out_dtype=exec.out_dtype)


def _int_zeros(tree):
    # Cotangents for the integer plan arrays: symbolic float0 zeros.
    return jax.tree.map(
        lambda x: np.zeros(x.shape, jax.dtypes.float0), tree)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _execute_vjp(meta, exec, fwd, bwd, vals, b, bias, residual):
    # The fwd/bwd bodies call the custom_vmap-wrapped ops: JAX vmaps these
    # bodies (it never differentiates them), so a vmapped batch axis lands
    # on the kernels' native batch grid instead of tracing into pallas_call.
    # ``exec`` is the normalized ExecutionConfig (frozen/hashable) — it
    # rides as a nondiff arg so the epilogue and dtypes reach both bodies.
    return _forward(meta, fwd, vals, b, exec, bias, residual,
                    vmappable=True)


def _execute_vjp_fwd(meta, exec, fwd, bwd, vals, b, bias, residual):
    ep = exec.epilogue
    if ep is None or ep.activation == "none":
        # Linear tail: fully fused forward; the backward needs no extra
        # saved intermediate (the chain rule through +bias/*scale/+residual
        # is dc-algebra only).
        out = _forward(meta, fwd, vals, b, exec, bias, residual,
                       vmappable=True)
        return out, (fwd, bwd, vals, b, bias, residual, None)
    # Nonlinear activation: fuse up to the pre-activation (C + bias, in acc
    # precision) and save it — the backward re-derives act'(pre) from it.
    # The act/scale/residual tail runs outside the kernel here; the
    # forward-only path (no grad) keeps the full fusion.
    pre_ep = dataclasses.replace(ep, activation="none", scale=None,
                                 residual=False)
    pre_exec = dataclasses.replace(
        exec, epilogue=None if pre_ep.is_identity() else pre_ep,
        out_dtype=exec.acc_dtype)
    pre = _forward(meta, fwd, vals, b, pre_exec,
                   bias if ep.bias else None, None, vmappable=True)
    tail = dataclasses.replace(ep, bias=False)
    with _obs.scope("spmm"), _obs.scope("unpad"):
        out = apply_epilogue(pre, tail, None,
                             residual if ep.residual else None)
        out = out.astype(jnp.dtype(exec.out_dtype))
    return out, (fwd, bwd, vals, b, bias, residual, pre)


def _execute_vjp_bwd(meta, exec, res, dc):
    with _obs.scope("spmm"):
        fwd, bwd, vals, b, bias, residual, pre = res
        ops = _ops()
        ep = exec.epilogue
        acc = jnp.dtype(exec.acc_dtype) if exec.acc_dtype else jnp.float32
        # Epilogue chain rule, peeled outside-in: out = act(C + bias) * scale
        # + residual  ⇒  d_residual = dc;  g = act'(pre) · (dc * scale) is the
        # cotangent of C (and of bias, row-summed).
        d_res = dc.astype(residual.dtype) \
            if ep is not None and ep.residual else None
        g = dc.astype(acc)
        if ep is not None:
            if ep.scale is not None:
                g = g * ep.scale
            if ep.activation != "none":
                _, act_vjp = jax.vjp(activation_fn(ep.activation),
                                     pre.astype(acc))
                g = act_vjp(g)[0]
        d_bias = None
        if ep is not None and ep.bias:
            d_bias = g.sum(axis=-1)
            if d_bias.ndim > 1:
                # Explicit leading batch dims: the bias is shared across them.
                d_bias = d_bias.sum(axis=tuple(range(d_bias.ndim - 1)))
            d_bias = d_bias.astype(bias.dtype)
        # dB = Aᵀ @ g through the transpose merge plan: the CSC view gets the
        # same equal-nonzero balancing as the forward pass (batched like it).
        db = ops.merge_execute_op(meta.k, exec.tk, exec.interpret, exec.impl)(
            bwd, vals, g).astype(b.dtype)
        # dvals = (g · Bᵀ) sampled at the pattern (gather-dot SDDMM),
        # reduced over any explicit batch dims — the values are shared
        # across the batch.  (Under vmap the axis is implicit and JAX itself
        # sums the cotangent for the unbatched values primal.)
        dvals = ops.sddmm_op(exec.interpret, exec.impl)(
            fwd["nz_rows"], fwd["nz_cols"], fwd["nz_valid"], g, b)
        if dvals.ndim > 1:
            dvals = dvals.sum(axis=tuple(range(dvals.ndim - 1)))
        return (_int_zeros(fwd), _int_zeros(bwd), dvals.astype(vals.dtype), db,
                d_bias, d_res)


_execute_vjp.defvjp(_execute_vjp_fwd, _execute_vjp_bwd)


def execute_plan(plan: SpmmPlan, vals: jax.Array, b: jax.Array,
                 exec: ExecutionConfig | None = None, *,
                 bias: jax.Array | None = None,
                 residual: jax.Array | None = None,
                 interpret=_UNSET, impl=_UNSET, tk=_UNSET) -> jax.Array:
    """Execute a prebuilt plan: C = A @ B with A's values given per call.

    Trace-safe (every static decision was captured at plan build) and
    differentiable in ``vals``, ``b``, ``bias`` and ``residual`` when the
    plan carries its transpose (``build_plan(..., with_transpose=True)``,
    the default).

    ``exec`` is the per-call :class:`ExecutionConfig` (implementation,
    interpret mode, K-tile cap, fused epilogue, accumulation/output
    dtypes); the bare ``interpret``/``impl``/``tk`` kwargs are pre-v1
    shims that warn once.  ``b`` may carry leading batch dims —
    ``(..., k, n) → (..., m, n)`` runs the whole stack through one kernel
    dispatch with shared values, and ``jax.vmap`` over the 2-D form lowers
    to the same batched path.

    ``bias (m,)`` / ``residual (..., m, n)`` feed the fused epilogue
    ``act(C + bias) * scale + residual`` — flags in ``exec.epilogue`` (an
    :class:`Epilogue`; auto-derived from the operands when unset) —
    applied at the kernels' accumulator flush in ``exec.acc_dtype`` (f32
    by default, also under bf16 inputs) with one cast to
    ``exec.out_dtype``.  One pass over C instead of a write + re-read per
    tail op.
    """
    exec = coalesce_exec("execute_plan", exec, impl=impl,
                         interpret=interpret, tk=tk)
    # Static shape guards: gathers clamp out-of-bounds indices silently, so
    # a stale plan would otherwise produce garbage instead of an error.
    if vals.shape != (plan.meta.nnz_pad,):
        raise ValueError(
            f"plan expects vals of shape ({plan.meta.nnz_pad},) for pattern "
            f"{plan.meta.shape}, got {vals.shape} — was the plan built for "
            "a different sparsity pattern?")
    if b.ndim < 2 or b.shape[-2] != plan.meta.k:
        raise ValueError(
            f"plan expects B of shape (..., {plan.meta.k}, n) for pattern "
            f"{plan.meta.shape}, got {b.shape}")
    exec = _resolve_exec("execute_plan", plan.meta.m, vals, b, exec,
                         bias, residual)
    if _trace._enabled:
        _record_dispatch(plan.meta, b, exec)
    if plan.bwd is None:
        # Forward-only plan: plain ops (keeps ordinary XLA autodiff for
        # impl="xla" callers; build with a transpose for vmap support).
        return _forward(plan.meta, plan.fwd, vals, b, exec, bias, residual,
                        vmappable=False)
    return _execute_vjp(plan.meta, exec, plan.fwd, plan.bwd, vals, b,
                        bias, residual)


# ------------------------------------------------------------ public API ---


def _check_plan_overrides(plan: SpmmPlan, policy: PlanPolicy) -> None:
    """Raise on an explicit policy that contradicts the supplied plan.

    A plan's method/t/l_pad were fixed at build time; silently ignoring a
    conflicting override would execute something other than what the call
    asked for (ISSUE 3: the silent-wrong-answer paths).
    """
    meta = plan.meta
    conflicts = []
    if policy.method != "auto" and policy.method != meta.method:
        conflicts.append(f"method={policy.method!r} (plan: {meta.method!r})")
    if policy.t is not None and policy.t != meta.t:
        conflicts.append(f"t={policy.t} (plan: {meta.t})")
    if policy.tl is not None and policy.tl != meta.tl:
        conflicts.append(f"tl={policy.tl} (plan: {meta.tl})")
    if policy.l_pad is not None and policy.l_pad != meta.l_pad:
        conflicts.append(f"l_pad={policy.l_pad} (plan: {meta.l_pad})")
    if policy.shards is not None:
        conflicts.append(f"shards={policy.shards} (plan: unsharded — build "
                         "a sharded plan via engine.get_plan or "
                         "SparseMatrix.shard)")
    if conflicts:
        raise ValueError(
            "spmm() overrides conflict with the supplied plan's static "
            "decisions: " + "; ".join(conflicts) + ". Rebuild the plan with "
            "these parameters (repro.core.build_plan / "
            "repro.engine.get_plan) or drop the overrides.")


def _check_sharded_overrides(plan, policy: PlanPolicy) -> None:
    """Raise on an explicit policy contradicting a sharded plan's statics."""
    meta = plan.meta
    conflicts = []
    if policy.shards is not None:
        spec = policy.shards
        if spec.resolved_n() != meta.n_shards:
            conflicts.append(f"shards n={spec.resolved_n()} "
                             f"(plan: {meta.n_shards})")
        if spec.dim != meta.dim:
            conflicts.append(f"shards dim={spec.dim!r} (plan: {meta.dim!r})")
    if policy.method != "auto":
        mismatched = sorted({lm.method for lm in meta.local_metas
                             if lm.method != policy.method})
        if mismatched:
            conflicts.append(f"method={policy.method!r} (plan shards use "
                             f"{mismatched})")
    for name in ("t", "tl", "l_pad"):
        want = getattr(policy, name)
        if want is None:
            continue
        got = sorted({getattr(lm, name) for lm in meta.local_metas},
                     key=lambda x: (x is None, x))
        if got != [want]:
            conflicts.append(f"{name}={want} (plan shards: {got})")
    if conflicts:
        raise ValueError(
            "spmm() overrides conflict with the supplied sharded plan's "
            "static decisions: " + "; ".join(conflicts) + ". Rebuild the "
            "sharded plan with these parameters (engine.get_plan with a "
            "shards= policy) or drop the overrides.")


def spmm(a: CSR, b: jax.Array, policy: PlanPolicy | None = None,
         exec: ExecutionConfig | None = None, *,
         plan: SpmmPlan | str | None = None,
         bias: jax.Array | None = None,
         residual: jax.Array | None = None,
         method=_UNSET, l_pad=_UNSET, t=_UNSET, heuristic=_UNSET,
         interpret=_UNSET, impl=_UNSET, tk=_UNSET) -> jax.Array:
    """Sparse(CSR) × dense = dense.  ``b`` is (..., k, n); returns (..., m, n).

    ``policy`` (a :class:`PlanPolicy`) holds every pattern-static decision
    — method, static kernel parameters, heuristic/TuneDB — and ``exec``
    (an :class:`ExecutionConfig`) the per-call backend knobs.  The bare
    ``method``/``l_pad``/``t``/``heuristic``/``interpret``/``impl``/``tk``
    kwargs are pre-v1 shims: they still work (warning once per process)
    but raise when combined with ``policy``/``exec``.

    Dispatch on ``plan``:

    * an ``SpmmPlan`` — execute it (jit-safe; ``a`` supplies only values).
      An explicit ``policy`` must agree with the plan's statics —
      conflicts raise instead of being silently ignored.
    * ``None`` (default) with concrete ``a`` — look up / build the
      pattern's plan in the engine cache, then execute.  Repeated calls
      with the same pattern (any values) never replan.
    * ``None`` with traced ``a``, or the string ``"inline"`` — plan inside
      the traced computation, every call (the paper's original per-call
      regime; benchmarks time it deliberately).  With a concrete ``a``
      the method and its parameters resolve through the same
      ``PlanPolicy.resolve`` as the planned path (TuneDB ladder included);
      under trace an explicit method is required — resolution is a
      host-side decision.

    ``bias``/``residual`` feed the epilogue ``act(C + bias) * scale +
    residual`` (flags in ``exec.epilogue``; see :func:`execute_plan`).
    On the planned and sharded paths the epilogue fuses into the kernels'
    output write; the inline path plans per call and applies it as a
    separate XLA tail — same math, none of the fusion.
    """
    policy = coalesce_policy("spmm", policy, method=method, t=t,
                             l_pad=l_pad, heuristic=heuristic)
    exec = coalesce_exec("spmm", exec, impl=impl, interpret=interpret,
                         tk=tk)
    if isinstance(plan, SpmmPlan):
        _check_plan_overrides(plan, policy)
        return execute_plan(plan, a.vals, b, exec, bias=bias,
                            residual=residual)
    if plan is not None and not isinstance(plan, str):
        from repro.distributed.spmm import ShardedSpmmPlan
        if isinstance(plan, ShardedSpmmPlan):
            _check_sharded_overrides(plan, policy)
            return plan.execute(a.vals, b, exec, bias=bias,
                                residual=residual)
    if plan is None and not _is_traced(a):
        from repro.engine import get_plan
        built = get_plan(a, policy=policy)
        if isinstance(built, SpmmPlan):
            return execute_plan(built, a.vals, b, exec, bias=bias,
                                residual=residual)
        return built.execute(a.vals, b, exec, bias=bias, residual=residual)
    if plan not in (None, "inline"):
        raise ValueError(f"plan must be an SpmmPlan, a ShardedSpmmPlan, "
                         f"None, or 'inline'; got {plan!r}")
    if policy.shards is not None:
        raise ValueError(
            "the inline (plan-per-call) spmm path cannot shard: sharding "
            "is a host-side plan decision. Build the sharded plan outside "
            "jit (repro.engine.get_plan with a shards= policy, or "
            "SparseMatrix.shard) and pass it through the jitted function.")
    if b.ndim != 2:
        raise ValueError(
            "the inline (plan-per-call) spmm path takes a 2-D B; batched "
            f"B {b.shape} needs a prebuilt plan — repro.engine.get_plan(a) "
            "— whose execution folds the batch into the kernel grid.")
    registry = _registry()
    m_name, t_val, tl_val, l_val = (policy.method, policy.t, policy.tl,
                                    policy.l_pad)
    extra = None
    if not _is_traced(a):
        # One resolution for both regimes: the inline path consults the
        # same TuneDB ladder / heuristic / parameter validation as the
        # planned path, so the two can never pick different kernels for
        # the same matrix.
        r = policy.resolve(a)
        m_name, t_val, tl_val, l_val = r.method, r.t, r.tl, r.l_pad
        extra = r.extra
    elif m_name == "auto":
        raise ValueError(
            "spmm(method='auto') on a traced CSR would need a host-side "
            "heuristic decision per call. Build a plan outside jit "
            "(repro.engine.get_plan) — the kernel choice is captured "
            "statically at plan-build time — or pass an explicit method.")
    spec = registry.get_method(m_name)
    if spec.inline is None:
        raise ValueError(
            f"SpMM method {m_name!r} has no inline (plan-per-call) form; "
            "build a plan instead: repro.engine.get_plan(a, policy=...)")
    exec = _resolve_exec("spmm", a.m, a.vals, b, exec, bias, residual)
    if _trace._enabled:
        _trace.event("dispatch", cat="dispatch", method=m_name,
                     impl=exec.impl, inline=True, n=int(b.shape[-1]),
                     tk=exec.tk, acc_dtype=exec.acc_dtype,
                     out_dtype=exec.out_dtype)
    with _obs.scope("spmm"):
        out = spec.inline(a, b, t=t_val, tl=tl_val, l_pad=l_val,
                          extra=extra, tk=exec.tk, interpret=exec.interpret,
                          impl=exec.impl)
        # The inline forms predate the fused tail: apply the epilogue (and
        # the dtype contract) post hoc — same math as the fused paths, none
        # of the fusion, which only matters in the plan-once serving regime
        # anyway.
        with _obs.scope("unpad"):
            ep = exec.epilogue
            if ep is not None:
                acc = jnp.dtype(exec.acc_dtype)
                out = apply_epilogue(
                    out.astype(acc), ep,
                    bias.astype(acc)[:, None] if ep.bias else None,
                    residual if ep.residual else None)
            return out.astype(jnp.dtype(exec.out_dtype))
