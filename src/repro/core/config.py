"""API v1 configuration split: plan policy vs. execution config.

The pre-v1 ``spmm`` accreted eight orthogonal kwargs.  They are really two
objects with different lifetimes:

* :class:`PlanPolicy` — **decided once per sparsity pattern, host-side**:
  which method (``"auto"`` resolves through the TuneDB ladder, then the
  registry's heuristic cost hooks), static kernel parameters (``t``,
  ``tl``, ``l_pad``), whether to build the transpose plan.  A policy is
  hashed into the engine's plan-cache key; resolving it
  (:meth:`PlanPolicy.resolve`) is the single choke point every plan
  request — planned *and* inline — funnels through, so the two paths can
  never pick different methods for the same matrix.

* :class:`ExecutionConfig` — **per call, trace-safe**: which
  implementation runs (``pallas`` | ``xla``), interpret mode, the K-tile
  cap ``tk``, the fused :class:`~repro.core.epilogue.Epilogue`, and the
  accumulator/output dtype overrides.  Changing it never invalidates a
  plan.

Canonical v1 signatures::

    spmm(a, b, policy=PlanPolicy(...), exec=ExecutionConfig(...))
    execute_plan(plan, vals, b, exec=ExecutionConfig(...))

The pre-v1 kwargs remain as deprecation shims for one release: they warn
once per process and raise when combined with the new-style objects.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, NamedTuple

from repro import obs as _obs
from repro.obs import trace as _trace

from .epilogue import Epilogue
from .heuristic import Heuristic

# Ladder-rung outcomes of PlanPolicy.resolve: explicit | exact | class |
# calibrated | analytic.  Always-on (plan-time, not per-execute):
# obs.report() derives ladder hit rates from this family.
_resolve_total = _obs.registry.counter(
    "plan_resolve_total", "PlanPolicy.resolve outcomes by ladder rung",
    labels=("rung", "method"))


def _canon_dtype(x) -> str | None:
    """Normalize a dtype-ish to its canonical name string (or None).

    Stored as a string so ExecutionConfig stays hashable and printable
    without importing jax at config time; resolved back to a dtype at the
    kernel boundary.
    """
    if x is None:
        return None
    if isinstance(x, str) and x in ("float32", "bfloat16", "float16",
                                    "float64"):
        return x
    import jax.numpy as jnp

    dt = jnp.dtype(x)
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(
            f"ExecutionConfig dtypes must be floating, got {dt.name!r}")
    return dt.name


class _DefaultTuneDB:
    """Sentinel: 'use the process-default TuneDB' (``engine.set_tunedb``).

    Distinct from ``None``, which explicitly opts out of measured
    resolution and falls back to the analytic heuristic.
    """

    _instance: "_DefaultTuneDB" | None = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return "DEFAULT_TUNEDB"


DEFAULT_TUNEDB = _DefaultTuneDB()


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How to shard an SpMM over devices (``PlanPolicy.shards``).

    ``n`` is the shard count (defaults to ``mesh.shape[axis]`` when a mesh
    is given); ``dim`` picks the nnz-balanced cut direction — ``"rows"``
    (data parallel: per-device row blocks, row-concatenated C) or
    ``"cols"`` (tensor parallel: per-device column slices of A against row
    blocks of B, partial sums all-reduced).  ``mesh`` is optional: without
    one, execution runs the per-shard loop on whatever devices hold the
    data (numerically identical); with one whose ``axis`` size matches
    ``n``, uniform plans execute as a single ``shard_map`` program.
    Hashable — a ShardSpec is part of the engine's plan-cache key.
    """

    n: int | None = None
    dim: str = "rows"
    axis: str | None = None        # default: "data" (rows) / "model"
    mesh: Any = None                  # jax.sharding.Mesh | None

    def __post_init__(self):
        if self.dim not in ("rows", "cols"):
            raise ValueError(
                f"ShardSpec.dim must be 'rows' or 'cols', got {self.dim!r}")
        if self.n is None and self.mesh is None:
            raise ValueError("ShardSpec needs n= (shard count) or mesh=")
        if self.n is not None and self.n < 1:
            raise ValueError(f"ShardSpec.n must be >= 1, got {self.n}")
        if self.axis is None:
            object.__setattr__(
                self, "axis", "model" if self.dim == "cols" else "data")
        if self.mesh is not None:
            if self.axis not in self.mesh.axis_names:
                raise ValueError(
                    f"ShardSpec axis {self.axis!r} is not an axis of the "
                    f"mesh (axes: {self.mesh.axis_names})")
            axis_size = self.mesh.shape[self.axis]
            if self.n is not None and self.n != axis_size:
                raise ValueError(
                    f"ShardSpec n={self.n} conflicts with mesh axis "
                    f"{self.axis!r} of size {axis_size}; drop n= to take "
                    "the axis size, or pass a matching mesh")

    def resolved_n(self) -> int:
        return self.n if self.n is not None else self.mesh.shape[self.axis]


def _as_shard_spec(shards) -> ShardSpec | None:
    if shards is None or isinstance(shards, ShardSpec):
        return shards
    if isinstance(shards, int):
        return ShardSpec(n=shards)
    raise TypeError(
        f"PlanPolicy.shards must be a ShardSpec, an int shard count, or "
        f"None; got {type(shards).__name__}")


class ResolvedPlan(NamedTuple):
    """A fully pinned-down plan request (every static decision made)."""

    method: str
    t: int
    tl: int
    l_pad: int | None
    extra: tuple                  # hashable method-specific statics


@dataclasses.dataclass(frozen=True)
class PlanPolicy:
    """How to *plan*: method selection + pattern-static parameters.

    All fields are host-side decisions captured at plan-build time and
    hashed into the engine cache key — never consulted inside jit.
    ``method="auto"`` resolves through the empirical TuneDB ladder (exact
    pattern → binned class → DB-calibrated threshold) and then the method
    registry's heuristic cost hooks; explicit methods name a registered
    ``MethodSpec`` (``repro.kernels.registry``).
    """

    method: str = "auto"
    t: int | None = None            # merge: nonzeroes per chunk
    tl: int | None = None           # rowsplit/rowgroup: row batch size
    l_pad: int | None = None        # rowsplit: static max row length
    heuristic: Heuristic | None = None
    tunedb: Any = DEFAULT_TUNEDB       # TuneDB | None (opt out) | default
    with_transpose: bool = True        # build the backward (CSC) plan
    shards: ShardSpec | None = None  # device sharding (int = n shards)

    def __post_init__(self):
        object.__setattr__(self, "shards", _as_shard_spec(self.shards))

    @classmethod
    def from_meta(cls, meta) -> "PlanPolicy":
        """The policy that replays an existing plan's full statics.

        Use to rebuild a plan identical to one in hand (checkpoint
        restore, ``ensure_spmm_plans``): method *and* tuned parameters
        are pinned, so nothing silently re-derives to defaults.
        """
        return cls(method=meta.method, t=meta.t, tl=meta.tl,
                   l_pad=meta.l_pad, with_transpose=meta.has_transpose)

    def resolved_tunedb(self):
        """The TuneDB this policy actually consults (may be None)."""
        if self.tunedb is DEFAULT_TUNEDB:
            from repro.engine import current_tunedb
            return current_tunedb()
        return self.tunedb

    def resolve(self, a) -> ResolvedPlan:
        """Pin down every pattern-static decision for a concrete CSR.

        The single source of truth for ``build_plan``, the engine cache
        key, and the inline (plan-per-call) ``spmm`` path — they can never
        disagree on the method or its static parameters.  Host-side only.
        """
        from repro.kernels import registry

        from .plan import _require_concrete, pattern_fingerprint

        if self.shards is not None:
            raise ValueError(
                "PlanPolicy.resolve() pins down the statics of ONE "
                "pattern; a sharded policy resolves per shard — each "
                "shard's local stats pick its own method — inside "
                "repro.distributed.spmm.build_sharded_plan (or via "
                "engine.get_plan, which dispatches on shards=).")
        _require_concrete(a, "PlanPolicy.resolve")
        method, t, l_pad = self.method, self.t, self.l_pad
        heuristic = self.heuristic
        tunedb = self.resolved_tunedb()
        # Which ladder rung decides the method (recorded below): explicit
        # requests skip the ladder entirely; "analytic" covers both the
        # no-TuneDB heuristic and a user-supplied Heuristic.
        rung = "explicit" if method != "auto" else "analytic"
        fallback = False
        if method == "auto" and tunedb is not None:
            registered = registry.method_names()
            rec = tunedb.lookup_exact(pattern_fingerprint(a))
            if rec is not None and rec.method not in registered:
                # Stale DB naming a method this process doesn't have
                # (e.g. built with a plugin): drop to the next rungs of
                # the ladder instead of crashing every plan on this
                # pattern.
                warnings.warn(
                    f"TuneDB exact record names unregistered method "
                    f"{rec.method!r} (registered: "
                    f"{', '.join(registered)}); falling back to "
                    "class/heuristic resolution", stacklevel=2)
                rec = None
            if rec is not None:
                # Exact hit: replay the measured winner and tuned params.
                method = rec.method
                t = rec.t if t is None else t
                l_pad = rec.l_pad if l_pad is None else l_pad
                rung = "exact"
            else:
                cls_method = tunedb.lookup_class_for(a)
                if cls_method is not None and cls_method in registered:
                    method = cls_method
                    rung = "class"
                elif heuristic is None:
                    heuristic = tunedb.heuristic()   # calibrated threshold
                    rung = "calibrated"
        auto_resolved = method != self.method     # ladder picked it
        if method == "auto":
            method = registry.choose_auto(a, heuristic or Heuristic())
            auto_resolved = True
        spec = registry.get_method(method)
        try:
            t, tl, l_pad, extra = spec.resolve_params(a, t=t, tl=self.tl,
                                                      l_pad=l_pad)
        except ValueError:
            if not auto_resolved:
                raise                             # the user asked for it
            # The ladder's winner rejects the caller's explicit params
            # (e.g. a TuneDB exact record replays "rowgroup" but the
            # caller passed a global l_pad, which only rowsplit-style
            # methods accept).  An "auto" request must not crash on a
            # constraint the caller never chose the method for — fall
            # back to the analytic choice among the core methods.
            method = registry.choose_auto(a, heuristic or Heuristic())
            spec = registry.get_method(method)
            t, tl, l_pad, extra = spec.resolve_params(
                a, t=self.t, tl=self.tl, l_pad=self.l_pad)
            rung, fallback = "analytic", True
        _resolve_total.labels(rung=rung, method=method).inc()
        if _trace._enabled:
            m_, k_ = a.shape
            _trace.event("plan.resolve", cat="plan", rung=rung,
                         method=method, m=int(m_), k=int(k_),
                         nnz_pad=int(a.nnz_pad), t=t, tl=tl,
                         l_pad=l_pad, fallback=fallback)
        return ResolvedPlan(method=method, t=t, tl=tl, l_pad=l_pad,
                            extra=extra)


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """How to *execute*: per-call, trace-safe backend knobs.

    ``impl``: ``"pallas"`` (the TPU kernels; interpret mode on CPU) or
    ``"xla"`` (the pure-XLA twins).  ``interpret``: force Pallas interpret
    mode (None: auto — interpret off TPU).  ``tk``: cap the K-tile of the
    B panel (None: the whole ``k`` resident in VMEM up to
    ``kernels.merge_spmm.tk_max()``, 98,304 rows on a v5e's 128 MiB of
    VMEM; the 16 MiB that once capped it at 1024 rows was Mosaic's default
    scoped limit, not the VMEM).

    ``epilogue``: a fused :class:`~repro.core.epilogue.Epilogue` spec —
    ``y = act(C + bias) * scale + residual`` applied at the kernels'
    accumulator flush; the ``bias``/``residual`` *arrays* travel as
    ``execute_plan``/``spmm`` call arguments.  ``acc_dtype``: accumulator
    precision (None → float32 — e.g. bf16 values/B with f32
    accumulation); ``out_dtype``: C's dtype (None → the promotion of the
    input dtypes).  Dtypes are stored as canonical name strings so the
    config stays hashable; anything ``jnp.dtype`` accepts is normalized.
    An ``acc_dtype`` the inputs don't fit in (f32 inputs, bf16
    accumulator) is rejected at call time — silent precision loss is a
    silent wrong answer.
    """

    impl: str = "pallas"
    interpret: bool | None = None
    tk: int | None = None
    epilogue: Epilogue | None = None
    acc_dtype: str | None = None
    out_dtype: str | None = None

    def __post_init__(self):
        object.__setattr__(self, "acc_dtype", _canon_dtype(self.acc_dtype))
        object.__setattr__(self, "out_dtype", _canon_dtype(self.out_dtype))
        if self.epilogue is not None and \
                not isinstance(self.epilogue, Epilogue):
            raise TypeError(
                "ExecutionConfig.epilogue must be a repro.core.Epilogue "
                f"(got {type(self.epilogue).__name__})")


DEFAULT_EXECUTION = ExecutionConfig()


# ------------------------------------------------------ deprecation shims ---

_UNSET = object()

_warned: set = set()


def _warn_deprecated(what: str, instead: str, *, stacklevel: int = 5) -> None:
    """DeprecationWarning, once per process per spelling.

    ``stacklevel`` is relative to ``warnings.warn`` inside this function;
    the default of 5 fits the ``spmm``/``execute_plan`` →
    ``coalesce_*`` → ``_coalesce`` chain — direct callers sitting fewer
    frames deep must pass their own so the warning points at the user's
    deprecated call site.
    """
    if what in _warned:
        return
    _warned.add(what)
    warnings.warn(f"{what} is deprecated; {instead}",
                  DeprecationWarning, stacklevel=stacklevel)


def reset_deprecation_warnings() -> None:
    """Forget which deprecations already warned (tests only)."""
    _warned.clear()


def _coalesce(context: str, new_name: str, new_obj, cls, legacy: dict):
    given = {k: v for k, v in legacy.items() if v is not _UNSET}
    if not given:
        return new_obj
    if new_obj is not None:
        raise ValueError(
            f"{context}: pass either {new_name}= or the legacy kwargs "
            f"{sorted(given)}, not both — the legacy kwargs are shims for "
            f"{cls.__name__} and cannot override it")
    for k in given:
        _warn_deprecated(
            f"{context}({k}=...)",
            f"pass {new_name}={cls.__name__}({k}=...) "
            "(see README.md: Migrating to API v1)")
    return cls(**given)


def coalesce_policy(context: str, policy: PlanPolicy | None, *,
                    method=_UNSET, t=_UNSET, l_pad=_UNSET,
                    heuristic=_UNSET) -> PlanPolicy:
    """Fold pre-v1 plan kwargs into a PlanPolicy (warn once; conflicts
    with an explicit ``policy=`` raise)."""
    out = _coalesce(context, "policy", policy, PlanPolicy,
                    dict(method=method, t=t, l_pad=l_pad,
                         heuristic=heuristic))
    return out if out is not None else PlanPolicy()


def coalesce_exec(context: str, exec_: ExecutionConfig | None, *,
                  impl=_UNSET, interpret=_UNSET,
                  tk=_UNSET) -> ExecutionConfig:
    """Fold pre-v1 execution kwargs into an ExecutionConfig."""
    out = _coalesce(context, "exec", exec_, ExecutionConfig,
                    dict(impl=impl, interpret=interpret, tk=tk))
    return out if out is not None else DEFAULT_EXECUTION
