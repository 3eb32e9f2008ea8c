"""Registry-driven static audit of the Pallas SpMM kernels.

For every registered ``MethodSpec`` × impl × representative dtype/epilogue
variant, this module traces the method's ``execute`` to a jaxpr *without
running it* and checks, statically:

* the lowering shape — ``impl="pallas"`` must stage exactly the expected
  number of ``pallas_call`` launches (one per merge/rowsplit dispatch,
  one per group for rowgroup) and ``impl="xla"`` none; the traced output
  dtype must match the requested ``out_dtype``/promotion rule;
* **VMEM and SMEM footprint** — each launch is re-modeled block-for-block
  from the kernel's BlockSpecs (double-buffered in/out blocks + scratch
  in VMEM; scalar-prefetch operands whole + double-buffered SMEM blocks
  in SMEM) and summed against the per-backend budgets, catching
  ``resolve_tk``/operand blowups before any compile;
* **grid/index-map in-bounds** — every index map is evaluated over every
  point of the static grid (with the real scalar-prefetch arrays, e.g.
  the merge ``tile`` stream) and each block must land inside its operand;
* **single-writer discipline** — the accumulator-flush predicate is
  enumerated over the grid and every output tile must be written exactly
  once (the invariant that replaces the paper's GPU carry-out fix-up);
* **accumulator dtype** — ``acc_dtype`` is never narrower than the
  promotion of the input dtypes (PR 6's runtime guard, proven per
  variant).

The launch models live with the kernels: each method's ``MethodSpec``
supplies them through its ``traffic`` hook (``repro.kernels.introspect``
— the same models feed ``repro.analysis.access`` and ``.traffic``), and
:data:`_AUDITS` is an override table (``register_audit``) for tests and
out-of-tree methods.  A method registered in ``repro.kernels.registry``
with neither is a *hard failure* (``K001``), not a silent skip — new
methods must either provide a model or explicitly inherit one.
:func:`audit_all` returns ``(rows, diagnostics)``; ``rows`` is the
per-launch report table that ``make analyze`` uploads as a CI artifact.
"""
from __future__ import annotations

import dataclasses
from collections import Counter as _Counter
from collections.abc import Callable

import numpy as np

from repro.kernels import merge_spmm as _ms
from repro.kernels.introspect import KernelBlock, KernelLaunch

from .diagnostics import Diagnostic

#: Static on-chip memory budgets per backend, bytes.  VMEM: the most a
#: launch's modeled blocks may take, which is the largest scoped limit the
#: kernels request (``merge_spmm.vmem_limit``: the blocks plus a headroom,
#: at most the VMEM) less that headroom — 124 MiB of a v5e TensorCore's
#: 128 MiB (16 MiB is Mosaic's default scoped limit, not the VMEM).  SMEM:
#: 1 MiB (the v5e compiler refuses a kernel whose prefetched scalars
#: exceed 1 MiB).  The audit models the TPU target — the CPU interpret
#: substrate has no such limit but must not mask a lowering that could
#: never fit real hardware.
VMEM_BUDGET_BYTES = {"tpu": _ms.V5E_VMEM_BYTES - _ms.VMEM_HEADROOM_BYTES}
SMEM_BUDGET_BYTES = {"tpu": 2 ** 20}

AUDIT_IMPLS = ("pallas", "xla")


@dataclasses.dataclass(frozen=True)
class Variant:
    """One representative dtype/epilogue corner audited per method."""

    name: str
    vals_dtype: str
    b_dtype: str
    acc_dtype: str
    out_dtype: str | None
    epilogue: object            # repro.core.Epilogue | None


def _variants():
    from repro.core.epilogue import Epilogue
    return (
        Variant("f32", "float32", "float32", "float32", None, None),
        Variant("bf16_acc32+epi", "bfloat16", "bfloat16", "float32",
                "bfloat16",
                Epilogue(bias=True, activation="gelu", residual=True)),
    )


# The model classes live next to the kernels (repro.kernels.introspect);
# these aliases keep the audit's public vocabulary and existing callers.
Block = KernelBlock
LaunchModel = KernelLaunch


#: method name -> model builder(plan, n, batch, variant, tk) ->
#: [LaunchModel] — *overrides* for the registry's ``MethodSpec.traffic``
#: hook (tests, out-of-tree methods).  Built-in methods ship their
#: models on the spec itself; a method with neither is K001.
_AUDITS: dict[str, Callable] = {}


def register_audit(name: str, models: Callable, *,
                   override: bool = False) -> None:
    """Override the launch models for a registered method (takes
    precedence over its ``MethodSpec.traffic`` hook)."""
    if name in _AUDITS and not override:
        raise ValueError(f"audit for method {name!r} already registered")
    _AUDITS[name] = models


# ----------------------------------------------------------- static checks ---


def _n_blocks(block: Block) -> int:
    return int(np.prod([
        -(-a // s) for a, s in zip(block.array_shape, block.shape)]))


def check_in_bounds(model: LaunchModel) -> list[str]:
    """Evaluate every index map over every grid point; returns violation
    strings (empty = proven in-bounds by enumeration)."""
    bad = []
    for point in np.ndindex(*model.grid):
        for blk in model.blocks:
            if blk.index_map is None:
                continue
            idx = blk.index_map(*point)
            for d, (bi, bs, asz) in enumerate(
                    zip(idx, blk.shape, blk.array_shape)):
                if bi < 0 or (int(bi) + 1) * bs > asz:
                    bad.append(
                        f"{blk.name}@grid{tuple(point)}: block index "
                        f"{tuple(int(i) for i in idx)} dim {d} outside "
                        f"operand {blk.array_shape}")
                    if len(bad) >= 5:
                        return bad
    return bad


def check_single_writer(model: LaunchModel) -> list[str]:
    """The flush predicate must write every output tile exactly once."""
    writes = _Counter()
    for point in np.ndindex(*model.grid):
        if model.flush(*point):
            writes[tuple(int(i) for i in model.out.index_map(*point))] += 1
    problems = []
    multi = {ix: c for ix, c in writes.items() if c != 1}
    if multi:
        some = list(multi.items())[:3]
        problems.append(f"tiles written != once: {some}")
    expected = _n_blocks(model.out)
    if len(writes) != expected:
        problems.append(
            f"{len(writes)} of {expected} output tiles ever flushed")
    return problems


def _promotes_ok(var: Variant) -> bool:
    import jax.numpy as jnp
    promoted = jnp.promote_types(var.vals_dtype, var.b_dtype)
    return jnp.promote_types(promoted, var.acc_dtype) == \
        jnp.dtype(var.acc_dtype)


def _count_pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                n += _count_pallas_calls(sub)
    return n


def _subjaxprs(v):
    if hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
        return [v.jaxpr]
    if hasattr(v, "eqns"):
        return [v]
    if isinstance(v, (list, tuple)):
        out = []
        for item in v:
            out.extend(_subjaxprs(item))
        return out
    return []


# -------------------------------------------------------------- the audit ---


@dataclasses.dataclass(frozen=True)
class AuditRow:
    """One line of the report table (per method × impl × variant)."""

    method: str
    impl: str
    variant: str
    launches: int
    grid_points: int
    vmem_bytes: int
    vmem_frac: float            # of the TPU budget (max over launches)
    ok: bool
    notes: str = ""


def _representative(m: int = 48, k: int = 192, batch: int = 2):
    """A small irregular pattern every method plans against: row lengths
    span [1, 24) so rowgroup gets several buckets and rowsplit a
    nontrivial L; k and n are sized so the audit's explicit ``tk`` makes
    the k-tile axis and the column-tile axis both multi-step."""
    import jax
    from repro.core.csr import random_csr
    a = random_csr(jax.random.PRNGKey(0), m, k, nnz_per_row=(1, 23))
    return a


def _trace_execute(spec, plan, var, impl, n, batch):
    """Trace the method's execute to a jaxpr + output aval (no run)."""
    import jax
    import jax.numpy as jnp
    meta, fwd = plan.meta, plan.fwd
    ep = var.epilogue
    vals = jnp.zeros((meta.nnz_pad,), var.vals_dtype)
    b = jnp.zeros((batch, meta.k, n), var.b_dtype)
    bias = jnp.zeros((meta.m,), var.b_dtype) \
        if ep is not None and ep.bias else None
    residual = jnp.zeros((batch, meta.m, n), var.b_dtype) \
        if ep is not None and ep.residual else None

    def f(vals, b, bias, residual):
        return spec.execute(meta, fwd, vals, b, tk=None, interpret=True,
                            impl=impl, epilogue=ep, bias=bias,
                            residual=residual, acc_dtype=var.acc_dtype,
                            out_dtype=var.out_dtype)

    jaxpr = jax.make_jaxpr(f)(vals, b, bias, residual)
    out = jax.eval_shape(f, vals, b, bias, residual)
    return jaxpr.jaxpr, out


def audit_method(name: str, *, n: int = 256, batch: int = 2,
                 tk: int | None = 64, backend: str = "tpu"):
    """Audit one registered method; returns ``(rows, diagnostics)``."""
    import jax.numpy as jnp
    from repro.core.plan import build_plan
    from repro.kernels import registry

    spec = registry.get_method(name)
    models_fn = _AUDITS.get(name, spec.traffic)
    rows, diags = [], []
    if models_fn is None:
        diags.append(Diagnostic(
            "K001", name,
            "registered method has no static launch model — set the "
            "MethodSpec.traffic hook or override via "
            "repro.analysis.kernel_audit.register_audit (the audit "
            "never skips silently)"))
        return rows, diags
    a = _representative()
    plan = build_plan(a, method=name)
    budget = VMEM_BUDGET_BYTES[backend]
    smem_budget = SMEM_BUDGET_BYTES[backend]
    for var in _variants():
        if not _promotes_ok(var):
            diags.append(Diagnostic(
                "K050", f"{name}/{var.name}",
                f"acc_dtype {var.acc_dtype} is narrower than the "
                f"promotion of ({var.vals_dtype}, {var.b_dtype})"))
        models = models_fn(plan, n, batch, var, tk)
        expect_odt = jnp.dtype(var.out_dtype) if var.out_dtype else \
            jnp.promote_types(var.vals_dtype, var.b_dtype)
        for impl in AUDIT_IMPLS:
            where = f"{name}/{impl}/{var.name}"
            notes, ok = [], True
            try:
                jaxpr, out = _trace_execute(spec, plan, var, impl, n,
                                            batch)
            except Exception as e:       # noqa: BLE001 — report, not die
                diags.append(Diagnostic(
                    "K010", where, f"tracing the kernel failed: {e!r}"))
                rows.append(AuditRow(name, impl, var.name, 0, 0, 0, 0.0,
                                     False, "trace failed"))
                continue
            n_calls = _count_pallas_calls(jaxpr)
            want_calls = len(models) if impl == "pallas" else 0
            if n_calls != want_calls:
                ok = False
                diags.append(Diagnostic(
                    "K011", where,
                    f"expected {want_calls} pallas_call launch(es) in "
                    f"the jaxpr, found {n_calls}"))
            if jnp.dtype(out.dtype) != expect_odt:
                ok = False
                diags.append(Diagnostic(
                    "K012", where,
                    f"traced output dtype {out.dtype} != requested "
                    f"{expect_odt}"))
            vmem = grid_pts = 0
            frac = 0.0
            if impl == "pallas":
                for model in models:
                    mb = model.vmem_bytes()
                    vmem = max(vmem, mb)
                    frac = max(frac, mb / budget)
                    grid_pts += int(np.prod(model.grid))
                    if mb > budget:
                        ok = False
                        diags.append(Diagnostic(
                            "K020", f"{where}:{model.label}",
                            f"modeled VMEM {mb} B exceeds the {backend} "
                            f"budget {budget} B"))
                    if model.smem_bytes() > smem_budget:
                        ok = False
                        diags.append(Diagnostic(
                            "K021", f"{where}:{model.label}",
                            f"modeled SMEM {model.smem_bytes()} B exceeds "
                            f"the {backend} budget {smem_budget} B"))
                    for viol in check_in_bounds(model):
                        ok = False
                        diags.append(Diagnostic(
                            "K030", f"{where}:{model.label}", viol))
                    for prob in check_single_writer(model):
                        ok = False
                        diags.append(Diagnostic(
                            "K040", f"{where}:{model.label}", prob))
                notes.append(f"{len(models)} launch(es)")
            rows.append(AuditRow(
                name, impl, var.name, want_calls if impl == "pallas"
                else 0, grid_pts, vmem, round(frac, 4), ok,
                "; ".join(notes)))
    return rows, diags


def merge_smem_bytes(n_chunks: int, t: int) -> int:
    """Modeled SMEM of one merge launch: the four prefetched chunk streams
    (``tile``, ``first``, ``last``, ``count``: 16 bytes a chunk) and the
    step's three ``(1, 1, t)`` index/value blocks, double-buffered."""
    return 16 * n_chunks + 3 * 2 * 4 * t


def merge_chunk_ceiling(*, backend: str = "tpu") -> int:
    """Most merge chunks whose scalar streams still fit SMEM, at the
    default chunk cap ``T_MAX``."""
    from repro.kernels.merge_spmm import T_MAX
    return (SMEM_BUDGET_BYTES[backend] - merge_smem_bytes(0, T_MAX)) // 16


def scale_rows(*, k: int = 29568) -> list[str]:
    """Informational serving-scale probe lines for the report (the
    representative audit proves the invariants; this states where the
    static models say the current lowering stops scaling)."""
    from repro.kernels.merge_spmm import T_MAX, resolve_tk, tk_max
    tk, n_k = resolve_tk(k, None)
    chunks = merge_chunk_ceiling()
    return [
        f"scale probe: k={k} resolves to tk={tk} ({n_k} K-tiles) — the "
        f"B panel takes {tk * 128 * 4 / 2 ** 20:.1f} MiB/buffer; a d_in "
        f"above {tk_max():,} streams in panels of that many rows",
        f"scale probe: merge's prefetched chunk streams cap a launch at "
        f"{chunks:,} chunks (~{chunks * T_MAX:,} nonzeros at T={T_MAX}) "
        "of the 1 MiB SMEM",
    ]


def audit_all(*, n: int = 256, batch: int = 2, tk: int | None = 64):
    """Audit every registered method; returns ``(rows, diagnostics)``.

    Coverage is bidirectional and loud: a registered method with neither
    a ``MethodSpec.traffic`` hook nor an ``_AUDITS`` override is K001; a
    stale ``_AUDITS`` override naming an unregistered method is K002.
    """
    from repro.kernels import registry
    rows, diags = [], []
    for name in registry.method_names():
        r, d = audit_method(name, n=n, batch=batch, tk=tk)
        rows.extend(r)
        diags.extend(d)
    for name in _AUDITS:
        if name not in registry.method_names():
            diags.append(Diagnostic(
                "K002", name,
                "kernel-audit entry for a method that is not registered "
                "(stale model?)"))
    return rows, diags


def format_report(rows, diags) -> str:
    """The per-method report table ``make analyze`` uploads to CI."""
    header = (f"{'method':<10} {'impl':<7} {'variant':<16} "
              f"{'launches':>8} {'grid':>6} {'vmem_kib':>9} "
              f"{'vmem%':>6} {'ok':>3}")
    lines = ["kernel audit report", header, "-" * len(header)]
    for r in rows:
        lines.append(
            f"{r.method:<10} {r.impl:<7} {r.variant:<16} "
            f"{r.launches:>8} {r.grid_points:>6} "
            f"{r.vmem_bytes / 1024:>9.1f} {r.vmem_frac * 100:>5.1f}% "
            f"{'ok' if r.ok else 'FAIL':>4}"
            + (f"  {r.notes}" if r.notes else ""))
    lines.extend(scale_rows())
    if diags:
        lines.append("")
        lines.append(f"{len(diags)} finding(s):")
        lines.extend(f"  {d}" for d in diags)
    else:
        lines.append("no findings")
    return "\n".join(lines)
