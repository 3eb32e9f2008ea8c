"""Plan-once / execute-many SpMM plans.

The paper's thesis is that CSR-native SpMM wins by paying for *planning*
(load-balanced work partitioning) instead of format conversion.  For the
motivating workload — a pruned weight whose sparsity pattern is frozen for
the lifetime of the model — even that planning cost should be paid once,
not once per jitted call.  ``SpmmPlan`` captures everything derived from
the pattern:

* the forward execute structure — built by the resolved method's
  registered ``build_structure`` hook (``repro.kernels.registry``): merge
  chunk layout, row-split ELL layout (with its static ``l_pad``),
  row-grouped per-bucket ELL blocks, or whatever a registered method
  defines (method-specific statics land in ``PlanMeta.extra``),
* the kernel choice (``PlanPolicy.resolve``: the TuneDB ladder and the
  §5.4 heuristic evaluated *statically at plan-build time*, so jitted
  code never host-syncs on a method decision),
* per-nonzero (row, col) coordinates for the values-cotangent SDDMM, and
* a *transpose plan*: the same merge-based equal-nonzero balancing applied
  to the CSC view of A, so the backward ``dB = Aᵀ @ dC`` inherits the
  paper's load-balance guarantees.

Plans are pytrees of int32 device arrays plus static ``PlanMeta`` — they
thread through ``jax.jit`` boundaries as ordinary arguments and live inside
model pytrees (``repro.models.sparse.SparseLinear``).  Values are *not*
part of a plan: they are re-applied per call via the ``slot_nz``
indirection, which is what makes a plan reusable across training steps that
update the values but not the pattern.

Build plans eagerly (outside jit) with ``build_plan`` or, cached per
pattern, with ``repro.engine.get_plan``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.analysis import _flags as _verify_flags
from repro.obs import trace as _trace

from .csr import CSR
from .heuristic import Heuristic

# Host seconds of each plan-build step, always on: a build runs in set-up,
# a handful of times per pattern, never on the execute path.  Steps:
# ``resolve`` (the policy ladder, ``engine/cache.py``), ``fingerprint``
# (pattern copied to the host and hashed), ``structure`` (the method's
# structure builder, plus the transpose plan when asked for) and
# ``row_ids`` (per-nonzero row ids built on the host and copied back).
# ``method`` is the resolved method; ``fingerprint`` runs before any
# method is known and records ``method=""``.
# The structure builders dispatch device work asynchronously: a step's
# time is its host time, and device work still in flight when it ends
# counts in whichever later step first waits for it (none of the build's
# own steps does; the caller's ``block_until_ready`` on the plan does).
_build_step = _obs.registry.histogram(
    "plan_build_step_seconds", "host seconds of each plan-build step",
    labels=("method", "step"))


@contextlib.contextmanager
def build_step(step: str, method: str = ""):
    """Time one plan-build step: a ``plan.step.<step>`` span (nested in
    any open ``plan.build``) and a ``plan_build_step_seconds``
    observation under ``method``.  A step that resolves the method sets
    it on the yielded record (``with build_step("resolve") as st: ...;
    st.method = r.method``)."""
    st = types.SimpleNamespace(method=method)
    t0 = time.perf_counter()
    with _trace.span(f"plan.step.{step}", cat="plan"):
        yield st
    _build_step.labels(method=st.method, step=step).observe(
        time.perf_counter() - t0)


@dataclasses.dataclass(frozen=True)
class PlanMeta:
    """Static (hashable) metadata of an SpmmPlan — safe as a jit constant."""

    method: str                  # a registered method name (e.g. "merge")
    shape: tuple[int, int]       # (m, k) of A
    nnz_pad: int                 # static nonzero capacity
    t: int                       # merge: nonzeroes per chunk
    tl: int                      # rowsplit: nonzeroes per row batch
    l_pad: int | None         # rowsplit: static max row length
    has_transpose: bool          # backward (CSC-view) plan present
    extra: tuple = ()            # method-specific statics (hashable), e.g.
                                 # rowgroup's ((m_g, l_g), ...) group table

    def __post_init__(self):
        # PlanMeta rides through jit as a static (hashable) constant; an
        # unhashable ``extra`` would otherwise surface much later as an
        # opaque "unhashable type" error deep inside jax's caching.  Fail
        # here, at construction, with the actual culprit named.
        try:
            hash(self.extra)
        except TypeError:
            raise TypeError(
                f"PlanMeta.extra must be hashable (it is a jit-static "
                f"constant), got {type(self.extra).__name__}: "
                f"{self.extra!r}. Use nested tuples instead of "
                "lists/dicts/arrays for method-specific statics."
            ) from None

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def k(self) -> int:
        return self.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SpmmPlan:
    """Pattern-derived execute state for C = A @ B (and its VJP)."""

    fwd: dict                    # forward structure + nz coordinate arrays
    bwd: dict | None          # transpose merge structure (CSC view)
    meta: PlanMeta = dataclasses.field(metadata=dict(static=True))

    @property
    def method(self) -> str:
        return self.meta.method

    @property
    def l_pad(self) -> int | None:
        return self.meta.l_pad


def _kernels():
    # deferred: repro.kernels imports repro.core.csr at module scope
    from repro.kernels import merge_spmm, rowsplit_spmm
    return merge_spmm, rowsplit_spmm


def _require_concrete(a: CSR, what: str) -> None:
    if isinstance(a.row_ptr, jax.core.Tracer) or \
            isinstance(a.col_ind, jax.core.Tracer):
        raise ValueError(
            f"{what} needs a concrete sparsity pattern, but the CSR is "
            "traced. Build the plan once outside jit (repro.engine.get_plan "
            "or repro.core.plan.build_plan) and pass the SpmmPlan through "
            "the jitted function — plans are ordinary pytrees.")


def transpose_pattern(a: CSR):
    """CSC view of A as a CSR matrix of Aᵀ, plus the nonzero permutation.

    Returns ``(a_t, perm)`` where ``a_t`` is a (k, m) CSR holding the same
    pattern transposed (vals are zeros — structure only) and ``perm`` is an
    (nnz_pad,) int32 map from transpose nonzero position to original
    nonzero position (``nnz_pad`` sentinel past the valid range, so
    ``vals_ext[perm]`` with an appended zero yields the transposed values).
    Host-side; pattern must be concrete.
    """
    rp = np.asarray(a.row_ptr)
    ci = np.asarray(a.col_ind)
    m, k = a.shape
    nnz = int(rp[-1])
    nnz_pad = a.nnz_pad
    rows = np.repeat(np.arange(m, dtype=np.int32), np.diff(rp))
    cols = ci[:nnz]
    perm_valid = np.argsort(cols, kind="stable")           # CSC order
    t_row_ptr = np.zeros(k + 1, np.int32)
    np.cumsum(np.bincount(cols, minlength=k), out=t_row_ptr[1:])
    t_col_ind = np.zeros(nnz_pad, np.int32)
    t_col_ind[:nnz] = rows[perm_valid]
    perm = np.full(nnz_pad, nnz_pad, np.int32)
    perm[:nnz] = perm_valid
    a_t = CSR(jnp.asarray(t_row_ptr), jnp.asarray(t_col_ind),
              jnp.zeros(nnz_pad, a.vals.dtype), (k, m))
    return a_t, jnp.asarray(perm)


def _compose_slots(slot_nz: jax.Array, perm: jax.Array,
                   nnz_pad: int) -> jax.Array:
    """Remap slot indices through a nonzero permutation (sentinel-safe)."""
    perm_ext = jnp.concatenate(
        [perm, jnp.full((1,), nnz_pad, jnp.int32)])
    return perm_ext[slot_nz]


def _policy_from_kwargs(policy, method, heuristic, t, tl, l_pad,
                        with_transpose, tunedb):
    """Unify the explicit-kwarg and PlanPolicy spellings of a request."""
    from .config import PlanPolicy

    if policy is not None:
        if (method, heuristic, t, tl, l_pad, tunedb) != \
                ("auto", None, None, None, None, None) or not with_transpose:
            raise ValueError(
                "pass either policy= or the explicit method/heuristic/t/tl/"
                "l_pad/tunedb/with_transpose kwargs, not both")
        return policy
    return PlanPolicy(method=method, heuristic=heuristic, t=t, tl=tl,
                      l_pad=l_pad, tunedb=tunedb,
                      with_transpose=with_transpose)


def resolve_static(a: CSR, *, method: str = "auto",
                   heuristic: Heuristic | None = None,
                   t: int | None = None, tl: int | None = None,
                   l_pad: int | None = None, tunedb=None, policy=None):
    """Pin down every pattern-static decision of a plan request.

    Legacy spelling of ``PlanPolicy.resolve`` (``repro.core.config``):
    returns ``(method, t, tl, l_pad)`` fully resolved — ``auto`` goes
    through the TuneDB ladder (exact → class → calibrated threshold) and
    then the method registry's heuristic cost hooks; per-method parameter
    defaults and validation (e.g. the rowsplit ``l_pad`` silent-truncation
    guard) come from each method's registered ``resolve_params`` hook.
    All host-side, never inside jit.  Single source of truth for
    ``build_plan`` and the engine cache key — they can never disagree.
    """
    policy = _policy_from_kwargs(policy, method, heuristic, t, tl, l_pad,
                                 True, tunedb)
    r = policy.resolve(a)
    return r.method, r.t, r.tl, r.l_pad


def build_plan(a: CSR, *, method: str = "auto",
               heuristic: Heuristic | None = None,
               t: int | None = None, tl: int | None = None,
               l_pad: int | None = None,
               with_transpose: bool = True, tunedb=None,
               policy=None, _resolved=None) -> SpmmPlan:
    """Build an SpmmPlan from a concrete CSR (once per sparsity pattern).

    The request — a ``PlanPolicy`` or the equivalent explicit kwargs —
    resolves through ``PlanPolicy.resolve`` (TuneDB ladder, registry cost
    hooks, per-method parameter validation), a static decision captured in
    the plan so execution never host-syncs on it.  The plan structure
    itself comes from the resolved method's registered ``build_structure``
    hook.  ``with_transpose`` additionally builds the CSC-view merge plan
    that powers the ``dB`` backward pass; forward-only callers can skip it.
    """
    from repro.kernels import registry

    merge_k, _ = _kernels()
    _require_concrete(a, "build_plan")
    policy = _policy_from_kwargs(policy, method, heuristic, t, tl, l_pad,
                                 with_transpose, tunedb)
    # ``_resolved``: a ResolvedPlan the caller (the engine cache) already
    # computed for this exact request — skips re-running the ladder and
    # per-method derivation (e.g. rowgroup's host-side bucketing).
    if _resolved is None:
        with build_step("resolve") as st:
            r = policy.resolve(a)
            st.method = r.method
    else:
        r = _resolved
    meta = PlanMeta(method=r.method, shape=a.shape, nnz_pad=a.nnz_pad,
                    t=r.t, tl=r.tl, l_pad=r.l_pad,
                    has_transpose=policy.with_transpose, extra=r.extra)
    nnz_pad = a.nnz_pad
    with build_step("structure", r.method):
        fwd = dict(registry.get_method(r.method).build_structure(a, meta))
        bwd = None
        if policy.with_transpose:
            a_t, perm = transpose_pattern(a)
            # The backward dB = Aᵀ @ dC always runs merge-based: equal-
            # nonzero balancing on the CSC view, independent of the forward
            # method.
            bwd = dict(merge_k.plan_merge_structure(a_t, t=r.t))
            # Backward slots index *original* vals: compose chunk slots
            # with the transpose permutation once, at build time.
            bwd["slot_nz"] = _compose_slots(bwd["slot_nz"], perm, nnz_pad)

    # Per-nonzero coordinates for the SDDMM values-cotangent (in-bounds
    # everywhere; validity carried separately).
    with build_step("row_ids", r.method):
        rp = np.asarray(a.row_ptr)
        nnz = int(rp[-1])
        nz_rows = np.zeros(nnz_pad, np.int32)
        nz_rows[:nnz] = np.repeat(np.arange(a.m, dtype=np.int32),
                                  np.diff(rp))
        # Host-built arrays join the pattern where it lies (a shard's
        # plan on its own device, repro.distributed.spmm).
        put = functools.partial(jax.device_put, device=a.col_ind.sharding) \
            if getattr(a.col_ind, "committed", False) else jnp.asarray
        fwd["nz_rows"] = put(nz_rows)
        fwd["nz_cols"] = a.col_ind
        fwd["nz_valid"] = put(np.arange(nnz_pad) < nnz)
    plan = SpmmPlan(fwd=fwd, bwd=bwd, meta=meta)
    if _verify_flags.verify_plans:
        # Opt-in debug hook (REPRO_VERIFY_PLANS=1): full host-side
        # structural verification of the freshly built plan.  One module
        # attribute read when off — the obs gating pattern.
        from repro.analysis.planlint import check_plan
        check_plan(plan, a)
    return plan


_fingerprint_memo: dict = {}


def pattern_fingerprint(a: CSR) -> str:
    """Content hash of the sparsity pattern (not the values).

    Two CSR matrices with equal fingerprints (and shapes) share every plan
    — this is the engine cache key, so retraced/re-pruned models with the
    same mask reuse plans instead of replanning.

    Memoized per live CSR object (identity-checked via weakref), so the
    O(nnz) device→host hash is paid once per object, not per call — a
    serving loop that holds one CSR hits the plan cache in O(1).  The
    hash itself is the ``fingerprint`` plan-build step.
    """
    import hashlib
    import weakref

    _require_concrete(a, "pattern_fingerprint")
    key = id(a)
    memo = _fingerprint_memo.get(key)
    if memo is not None and memo[0]() is a:
        return memo[1]
    with build_step("fingerprint"):
        h = hashlib.sha1()
        h.update(np.asarray(a.row_ptr).tobytes())
        h.update(np.asarray(a.col_ind).tobytes())
        fp = h.hexdigest()
    try:
        ref = weakref.ref(a, lambda _, k=key: _fingerprint_memo.pop(k, None))
    except TypeError:       # object not weakref-able: skip the memo
        return fp
    _fingerprint_memo[key] = (ref, fp)
    return fp
