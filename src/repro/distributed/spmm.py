"""Device-sharded SpMM: nnz-balanced row/column shards with per-shard plans.

The paper's core design principle — give every processor an equal number
of *nonzeroes*, not an equal number of rows (§4, ``core/partition.py``) —
lifted from the Pallas-grid level to the device level.  A sparse matrix is
cut into contiguous row ranges (or, for the tensor-parallel variant,
column ranges) holding ~equal nonzero counts via the same
``searchsorted``-on-``row_ptr`` machinery as ``partition_spmm``; each
shard gets its *own* :class:`~repro.core.plan.SpmmPlan`, resolved through
the method registry and TuneDB ladder independently — a shard holding a
few dense rows and a shard holding many sparse rows can (and should) pick
different kernels, which is the whole point of balance-aware sharding.

Execution:

* ``dim="rows"`` (data parallel): every device runs its local planned
  kernel on its row block against the replicated dense ``B``; ``C`` is
  the row concatenation of the local blocks.
* ``dim="cols"`` (tensor parallel): ``A`` is column-sharded by nnz, each
  device multiplies its column slice against its row block of ``B`` and
  the rank-``m`` partial sums are all-reduced (``lax.psum``) over the
  mesh axis.

When every shard resolves to the same method and static parameters
(shapes are unified by padding rows/nonzeroes to the per-shard maxima),
the whole sharded multiply is one ``shard_map`` dispatch over the mesh
axis — a single SPMD program, differentiable end to end (the per-shard
``custom_vjp`` plans run inside the mapped body; the replicated-``B``
cotangent is psum'd by shard_map's transpose).  Heterogeneous shards
(different methods, or rowgroup's per-shard group tables) fall back to a
per-shard loop that is numerically identical and still differentiable —
correctness never depends on the mesh.

Plans are built through ``repro.engine``'s cache: each shard's local
pattern lands as its own entry (keyed on the shard's fingerprint), and
the :class:`ShardedSpmmPlan` itself is cached under the global pattern +
shard spec, so re-sharding with a different mesh size can never poison
either level.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs as _obs
from repro.analysis import _flags as _verify_flags
from repro.core.config import (ExecutionConfig, PlanPolicy, _UNSET,
                               coalesce_exec)
from repro.core.csr import CSR
from repro.core.plan import SpmmPlan, build_plan
from repro.core.spmm import execute_plan
from repro.obs import scope
from repro.obs import trace as _trace

# Shard-balance gauges are plan-time (amortized) and stay always-on; the
# per-execute counter below is gated on the tracing flag like the core
# dispatch path.
_shard_imbalance = _obs.registry.gauge(
    "shard_nnz_imbalance", "max/mean nnz ratio of the last sharded build",
    labels=("dim",))
_sharded_execute = _obs.registry.counter(
    "sharded_execute_total", "execute_sharded dispatches by path",
    labels=("path",))


def _nnz_cuts(ptr: np.ndarray, n_shards: int) -> np.ndarray:
    """Cut positions splitting ``ptr``'s span into ~equal-nnz ranges.

    ``ptr`` is any monotone prefix-sum array (``row_ptr`` for row shards,
    the CSC column pointer for column shards).  Returns ``n_shards + 1``
    monotone boundaries with ``bounds[0] == 0`` and ``bounds[-1] ==
    len(ptr) - 1``; each boundary is the row containing the ideal cut
    nonzero — the same ``searchsorted`` rule as ``partition_spmm``, so
    every range's nonzero count is within one max-row-length of the ideal
    ``nnz / n_shards``.
    """
    m = ptr.shape[0] - 1
    nnz = int(ptr[-1])
    targets = (np.arange(1, n_shards, dtype=np.int64) * nnz) // n_shards
    cuts = np.searchsorted(ptr, targets, side="right").astype(np.int64) - 1
    bounds = np.concatenate([[0], np.minimum(cuts, m), [m]])
    return np.maximum.accumulate(bounds)


@dataclasses.dataclass(frozen=True)
class CsrShards:
    """Host-side result of :func:`shard_csr_by_nnz`.

    ``csrs`` are the per-shard local patterns, padded to uniform static
    shapes (rows to the max shard row count, nonzeroes to the max shard
    nnz) so that same-method plans can stack into one SPMD dispatch.
    ``vals_slots[i]`` gathers shard ``i``'s local values out of the
    *global* value vector (sentinel ``nnz_pad`` → an appended zero), which
    is what keeps the sharded execution differentiable in the shared
    values.  For ``dim="cols"``, ``b_rows[i]`` gathers shard ``i``'s row
    block of ``B`` (sentinel ``k`` → an appended zero row).
    """

    dim: str                        # "rows" | "cols"
    shape: tuple[int, int]          # global (m, k)
    nnz_pad: int                    # global static nonzero capacity
    bounds: tuple[int, ...]         # n_shards+1 cuts over rows (or cols)
    csrs: tuple[CSR, ...]           # padded local patterns, uniform shapes
    vals_slots: tuple[jax.Array, ...]
    b_rows: tuple[jax.Array, ...] | None   # cols-dim only

    @property
    def n_shards(self) -> int:
        return len(self.csrs)

    def sizes(self) -> tuple[int, ...]:
        """True (unpadded) rows/cols per shard."""
        return tuple(self.bounds[i + 1] - self.bounds[i]
                     for i in range(self.n_shards))

    def unpadded(self, i: int) -> CSR:
        """Shard ``i`` without the uniform-shape padding.

        This is the view method resolution must see: the padded ``csrs``
        carry empty filler rows that dilute a shard's local stats (a
        3-dense-row shard padded to 500 rows looks sparse to ``d =
        nnz/m``), which would defeat per-shard method selection.
        """
        c = self.csrs[i]
        if self.dim == "cols":          # columns padded: d is unaffected
            return c
        rows = self.bounds[i + 1] - self.bounds[i]
        return CSR(c.row_ptr[:rows + 1], c.col_ind, c.vals, (rows, c.shape[1]))

    def nnz_per_shard(self) -> tuple[int, ...]:
        return tuple(int(np.asarray(c.row_ptr)[-1]) for c in self.csrs)


def _require_host(a: CSR) -> None:
    from repro.core.plan import _require_concrete
    _require_concrete(a, "shard_csr_by_nnz")


def shard_csr_by_nnz(a: CSR, n_shards: int, *, dim: str = "rows") -> CsrShards:
    """Cut ``a`` into ``n_shards`` contiguous ranges of ~equal nonzeroes.

    ``dim="rows"``: contiguous row ranges (each shard a ``(max_rows, k)``
    CSR — trailing empty rows pad shards to a common height).
    ``dim="cols"``: contiguous column ranges of the CSC view (each shard a
    ``(m, max_cols)`` CSR with columns remapped to shard-local ids).
    Host-side; the pattern must be concrete.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if dim not in ("rows", "cols"):
        raise ValueError(f"shard dim must be 'rows' or 'cols', got {dim!r}")
    _require_host(a)
    m, k = a.shape
    rp = np.asarray(a.row_ptr)
    ci = np.asarray(a.col_ind)
    nnz = int(rp[-1])
    if dim == "rows":
        bounds = _nnz_cuts(rp, n_shards)
        max_rows = int(np.max(np.diff(bounds))) if n_shards else 0
        loc_nnz = [int(rp[bounds[i + 1]] - rp[bounds[i]])
                   for i in range(n_shards)]
        loc_pad = max(max(loc_nnz, default=0), 1)
        csrs, slots = [], []
        for i in range(n_shards):
            r0, r1 = int(bounds[i]), int(bounds[i + 1])
            lrp = np.zeros(max_rows + 1, np.int32)
            lrp[:r1 - r0 + 1] = rp[r0:r1 + 1] - rp[r0]
            lrp[r1 - r0 + 1:] = lrp[r1 - r0]      # padded rows are empty
            lci = np.zeros(loc_pad, np.int32)
            lci[:loc_nnz[i]] = ci[rp[r0]:rp[r1]]
            csrs.append(CSR(jnp.asarray(lrp), jnp.asarray(lci),
                            jnp.zeros(loc_pad, a.vals.dtype), (max_rows, k)))
            slot = np.full(loc_pad, a.nnz_pad, np.int32)
            slot[:loc_nnz[i]] = np.arange(rp[r0], rp[r1], dtype=np.int32)
            slots.append(jnp.asarray(slot))
        return CsrShards(dim="rows", shape=a.shape, nnz_pad=a.nnz_pad,
                         bounds=tuple(int(b) for b in bounds),
                         csrs=tuple(csrs), vals_slots=tuple(slots),
                         b_rows=None)

    # dim == "cols": balance over the CSC view's column nonzero counts.
    rows_all = np.repeat(np.arange(m, dtype=np.int32), np.diff(rp))
    cols_all = ci[:nnz]
    col_ptr = np.zeros(k + 1, np.int64)
    np.cumsum(np.bincount(cols_all, minlength=k), out=col_ptr[1:])
    bounds = _nnz_cuts(col_ptr, n_shards)
    max_cols = int(np.max(np.diff(bounds))) if n_shards else 0
    max_cols = max(max_cols, 1)
    sels = [(cols_all >= bounds[i]) & (cols_all < bounds[i + 1])
            for i in range(n_shards)]
    loc_pad = max(max((int(s.sum()) for s in sels), default=0), 1)
    csrs, slots, b_rows = [], [], []
    for i in range(n_shards):
        c0, c1 = int(bounds[i]), int(bounds[i + 1])
        sel = sels[i]
        pos = np.nonzero(sel)[0].astype(np.int32)  # row-major order kept
        lrp = np.zeros(m + 1, np.int32)
        np.cumsum(np.bincount(rows_all[sel], minlength=m), out=lrp[1:])
        lci = np.zeros(loc_pad, np.int32)
        lci[:pos.shape[0]] = cols_all[sel] - c0
        csrs.append(CSR(jnp.asarray(lrp), jnp.asarray(lci),
                        jnp.zeros(loc_pad, a.vals.dtype), (m, max_cols)))
        slot = np.full(loc_pad, a.nnz_pad, np.int32)
        slot[:pos.shape[0]] = pos
        slots.append(jnp.asarray(slot))
        rows_idx = np.full(max_cols, k, np.int32)   # sentinel: zero row of B
        rows_idx[:c1 - c0] = np.arange(c0, c1, dtype=np.int32)
        b_rows.append(jnp.asarray(rows_idx))
    return CsrShards(dim="cols", shape=a.shape, nnz_pad=a.nnz_pad,
                     bounds=tuple(int(b) for b in bounds),
                     csrs=tuple(csrs), vals_slots=tuple(slots),
                     b_rows=tuple(b_rows))


# ------------------------------------------------------------------ plans ---


@dataclasses.dataclass(frozen=True)
class ShardedMeta:
    """Static (hashable) metadata of a ShardedSpmmPlan."""

    shape: tuple[int, int]          # global (m, k)
    nnz_pad: int                    # global static nonzero capacity
    dim: str                        # "rows" | "cols"
    bounds: tuple[int, ...]
    axis: str                       # mesh axis name
    mesh: jax.sharding.Mesh | None
    uniform: bool                   # all shards share method + statics
    local_metas: tuple              # one PlanMeta per shard

    def __post_init__(self):
        # Like PlanMeta: this is a jit-static constant — an unhashable
        # field must fail loudly at assembly, not inside jax's cache.
        try:
            hash((self.bounds, self.local_metas))
        except TypeError:
            raise TypeError(
                "ShardedMeta must be hashable (it is a jit-static "
                f"constant): bounds={self.bounds!r} and every local "
                "PlanMeta must be built from tuples, not lists/arrays."
            ) from None

    @property
    def n_shards(self) -> int:
        return len(self.local_metas)

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def k(self) -> int:
        return self.shape[1]

    @property
    def method(self) -> str:
        methods = {lm.method for lm in self.local_metas}
        return methods.pop() if len(methods) == 1 else "mixed"

    @property
    def l_pad(self) -> int | None:
        pads = {lm.l_pad for lm in self.local_metas}
        return pads.pop() if len(pads) == 1 else None

    @property
    def has_transpose(self) -> bool:
        return all(lm.has_transpose for lm in self.local_metas)

    def spmd_mesh(self):
        """The mesh to shard_map over, or None (per-shard loop)."""
        mesh = self.mesh
        if (not self.uniform or mesh is None
                or self.axis not in mesh.axis_names
                or mesh.shape[self.axis] != self.n_shards):
            return None
        return mesh


@dataclasses.dataclass(frozen=True)
class ShardedSpmmPlan:
    """Per-shard SpmmPlans + the value/B gathers that stitch them together.

    A pytree (the shard layout is static aux data), so it lives inside
    model pytrees and passes through jit boundaries exactly like a
    single-device ``SpmmPlan``.  A plan that runs as one SPMD program
    flattens to its stacked leaves: each leaf the shard-major
    concatenation of the shards' leaves along their first axis, sharded
    over the mesh axis so that shard ``i``'s block is the array the shard's
    plan was built as, on its own device (:meth:`_stacked`).  A jitted
    caller then receives every shard where it lives, and no device holds
    another's.  Other plans flatten to the per-shard plans and gather
    indices.  Execute with :func:`execute_sharded` (or ``A @ B`` on a
    sharded ``SparseMatrix``).
    """

    shards: tuple[SpmmPlan, ...]
    vals_slots: tuple[jax.Array, ...]
    b_rows: tuple[jax.Array, ...] | None
    meta: ShardedMeta

    @property
    def method(self) -> str:
        return self.meta.method

    def execute(self, vals: jax.Array, b: jax.Array,
                exec: ExecutionConfig | None = None, *,
                bias: jax.Array | None = None,
                residual: jax.Array | None = None) -> jax.Array:
        return execute_sharded(self, vals, b, exec, bias=bias,
                               residual=residual)

    def __getattr__(self, name):
        # A plan rebuilt from its stacked leaves (``_unflatten_sharded``)
        # derives the per-shard views on first use only: the SPMD path
        # never needs them, and slicing eagerly would copy every shard.
        cache = self.__dict__.get("_stack_cache")
        if name not in ("shards", "vals_slots", "b_rows") or cache is None:
            raise AttributeError(name)
        n = self.meta.n_shards

        def block(x, i):
            d0 = x.shape[0] // n
            return x[i * d0:(i + 1) * d0]
        stacked_plan, slot_stack, brow_stack = cache
        object.__setattr__(self, "shards", tuple(
            jax.tree.map(lambda x, i=i: block(x, i), stacked_plan)
            for i in range(n)))
        object.__setattr__(self, "vals_slots",
                           tuple(block(slot_stack, i) for i in range(n)))
        object.__setattr__(self, "b_rows", None if brow_stack is None else
                           tuple(block(brow_stack, i) for i in range(n)))
        return self.__dict__[name]

    # Stacked leaves for the SPMD path, memoized per live (concrete) plan
    # object so the execute-many regime stacks once, not per call.  Traced
    # leaves are never cached (tracers must not outlive their trace).
    def _stacked(self):
        cached = getattr(self, "_stack_cache", None)
        if cached is not None:
            return cached
        leaves = [self.shards, self.vals_slots, self.b_rows]
        mesh = self.meta.spmd_mesh()
        concrete = not any(isinstance(x, jax.core.Tracer)
                           for x in jax.tree.leaves(leaves))
        if concrete and mesh is not None:
            # Each shard's block is its own array, moved only if it was
            # built elsewhere than on its device (_shard_devices).
            sh = NamedSharding(mesh, P(self.meta.axis))

            def stack(*xs):
                d0 = xs[0].shape[0]
                shape = (len(xs) * d0,) + xs[0].shape[1:]
                return jax.make_array_from_single_device_arrays(
                    shape, sh, [jax.device_put(xs[(ix[0].start or 0) // d0],
                                               d)
                                for d, ix in
                                sh.addressable_devices_indices_map(
                                    shape).items()])
        else:
            def stack(*xs):
                return jnp.concatenate(xs)
        stacked_plan = jax.tree.map(stack, *self.shards)
        slot_stack = stack(*self.vals_slots)
        brow_stack = stack(*self.b_rows) if self.b_rows else None
        out = (stacked_plan, slot_stack, brow_stack)
        if concrete:
            object.__setattr__(self, "_stack_cache", out)
        return out


def _flatten_sharded(sp):
    if sp.meta.spmd_mesh() is not None:
        return sp._stacked(), sp.meta
    return (sp.shards, sp.vals_slots, sp.b_rows), sp.meta


def _unflatten_sharded(meta, children):
    sp = object.__new__(ShardedSpmmPlan)
    object.__setattr__(sp, "meta", meta)
    if meta.spmd_mesh() is not None:
        # Stacked leaves: kept as the SPMD operands, also under trace.
        object.__setattr__(sp, "_stack_cache", tuple(children))
    else:
        object.__setattr__(sp, "shards", children[0])
        object.__setattr__(sp, "vals_slots", children[1])
        object.__setattr__(sp, "b_rows", children[2])
    return sp


jax.tree_util.register_pytree_node(
    ShardedSpmmPlan, _flatten_sharded, _unflatten_sharded)


def _unify_params(rs) -> tuple:
    """Static params every shard can run: the per-shard maxima.

    A larger ``l_pad`` is valid for every rowsplit-style shard (its rows
    pad further), any ``t``/``tl`` is valid everywhere, so the maxima are
    the cheapest params that make same-method shards shape-compatible for
    one stacked SPMD dispatch.
    """
    t = max(r.t for r in rs)
    tl = max(r.tl for r in rs)
    pads = [r.l_pad for r in rs if r.l_pad is not None]
    return t, tl, (max(pads) if pads else None)


def _shard_devices(mesh, axis: str, n: int) -> list:
    """The first device holding shard ``i`` of ``n`` split over ``axis``
    of ``mesh``, for each ``i``."""
    sh = NamedSharding(mesh, P(axis))
    first: dict = {}
    for d, ix in sh.addressable_devices_indices_map((n,)).items():
        first.setdefault(ix[0].start or 0, d)
    return [first[i] for i in range(n)]


def build_sharded_plan(a: CSR, policy: PlanPolicy,
                       cache=None) -> ShardedSpmmPlan:
    """Shard ``a`` by nnz and plan each shard independently.

    Each shard's method resolves through the full ladder (TuneDB exact →
    class → calibrated threshold → registry cost hooks) *on its own local
    stats* — an imbalanced matrix can mix kernels across shards.  When the
    shards agree on a method, their static parameters are unified to the
    per-shard maxima so the plans stack into one ``shard_map`` program
    (``meta.uniform``); otherwise execution falls back to the per-shard
    loop.  ``cache`` (a ``repro.engine.PlanCache``) makes every local plan
    a distinct cache entry keyed on the shard's own pattern fingerprint.
    """
    spec = policy.shards
    if spec is None:
        raise ValueError("build_sharded_plan needs a policy with shards= "
                         "set (a repro.core.ShardSpec)")
    from repro.kernels import registry

    n = spec.resolved_n()
    local_policy = dataclasses.replace(policy, shards=None)
    with _trace.span("plan.build_sharded", cat="plan", n_shards=n,
                     dim=spec.dim, m=int(a.shape[0]),
                     k=int(a.shape[1])) as sp:
        shards = shard_csr_by_nnz(a, n, dim=spec.dim)
        nnz_per = shards.nnz_per_shard()
        mean_nnz = sum(nnz_per) / max(len(nnz_per), 1)
        imbalance = (max(nnz_per) / mean_nnz) if mean_nnz > 0 else 1.0
        _shard_imbalance.labels(dim=spec.dim).set(imbalance)
        # Resolve on the *unpadded* local patterns: a shard's method must
        # come from its true local stats, not stats diluted by
        # shape-padding.
        resolved = [local_policy.resolve(shards.unpadded(i))
                    for i in range(n)]
        sp.set(methods=[r.method for r in resolved],
               nnz_per_shard=list(nnz_per),
               nnz_imbalance=round(imbalance, 4))
    methods = {r.method for r in resolved}
    stackable = False
    slots = shards.vals_slots
    if len(methods) == 1:
        # One method everywhere: unify the static params and check that
        # the method derives identical method-specific statics on the
        # shape-padded locals — then the plans stack into one SPMD body.
        t, tl, l_pad = _unify_params(resolved)
        mspec = registry.get_method(resolved[0].method)
        extras = [mspec.resolve_params(c, t=t, tl=tl, l_pad=l_pad)[3]
                  for c in shards.csrs]
        stackable = all(e == extras[0] for e in extras)
    if stackable:
        pinned = [PlanPolicy(method=resolved[0].method, t=t, tl=tl,
                             l_pad=l_pad, tunedb=None,
                             with_transpose=policy.with_transpose)] * n
        build_csrs = shards.csrs
        mesh = spec.mesh
        if mesh is not None and spec.axis in mesh.axis_names and \
                mesh.shape[spec.axis] == n:
            # One program over the mesh: each shard is planned on its own
            # device, where its plan then stays (no device ever holds, or
            # builds, another shard's plan).
            homes = _shard_devices(mesh, spec.axis, n)
            build_csrs = [jax.device_put(c, d)
                          for c, d in zip(build_csrs, homes)]
            slots = tuple(jax.device_put(s, d) for s, d in zip(slots, homes))
    else:
        # Heterogeneous shards run the per-shard loop, where shape
        # padding buys nothing and can cost plenty (a rowsplit shard
        # would ELL-pad every filler row) — plan the true local patterns.
        pinned = [PlanPolicy(method=r.method, t=r.t, tl=r.tl, l_pad=r.l_pad,
                             tunedb=None,
                             with_transpose=policy.with_transpose)
                  for r in resolved]
        build_csrs = [shards.unpadded(i) for i in range(n)]
    if cache is not None:
        plans = tuple(cache.get(c, p) for c, p in zip(build_csrs, pinned))
    else:
        plans = tuple(build_plan(c, policy=p)
                      for c, p in zip(build_csrs, pinned))
    uniform = stackable and all(p.meta == plans[0].meta for p in plans)
    if _trace._enabled:
        _trace.event("plan.sharded_assembled", cat="plan", n_shards=n,
                     dim=spec.dim, uniform=uniform,
                     methods=[p.meta.method for p in plans])
    meta = ShardedMeta(shape=a.shape, nnz_pad=a.nnz_pad, dim=spec.dim,
                       bounds=shards.bounds, axis=spec.axis, mesh=spec.mesh,
                       uniform=uniform, local_metas=tuple(p.meta
                                                          for p in plans))
    plan = ShardedSpmmPlan(shards=plans, vals_slots=slots,
                           b_rows=shards.b_rows, meta=meta)
    if _verify_flags.verify_plans:
        # REPRO_VERIFY_PLANS debug hook: the per-shard plans were each
        # verified by build_plan; this checks the assembly — bounds tile
        # the global span, the values gather covers every global nonzero
        # exactly once, b_rows slice per shard (repro.analysis.planlint).
        from repro.analysis.planlint import check_plan
        check_plan(plan, a)
    return plan


# -------------------------------------------------------------- execution ---


def _local_vals(vals: jax.Array, slot: jax.Array) -> jax.Array:
    vals_ext = jnp.concatenate([vals, jnp.zeros(1, vals.dtype)])
    return vals_ext[slot]


def _local_b(b: jax.Array, rows: jax.Array) -> jax.Array:
    zero_row = jnp.zeros(b.shape[:-2] + (1, b.shape[-1]), b.dtype)
    b_ext = jnp.concatenate([b, zero_row], axis=-2)
    return jnp.take(b_ext, rows, axis=-2)


def _concat_rows(outs, bounds):
    """Row-concatenate per-shard outputs, dropping each shard's pad rows."""
    sizes = [bounds[i + 1] - bounds[i] for i in range(len(outs))]
    return jnp.concatenate(
        [o[..., :sz, :] for o, sz in zip(outs, sizes)], axis=-2)


def execute_sharded(plan: ShardedSpmmPlan, vals: jax.Array, b: jax.Array,
                    exec: ExecutionConfig | None = None, *,
                    bias: jax.Array | None = None,
                    residual: jax.Array | None = None,
                    interpret=_UNSET, impl=_UNSET, tk=_UNSET) -> jax.Array:
    """C = A @ B through a sharded plan, with A's *global* values per call.

    Mirrors ``core.spmm.execute_plan``: trace-safe, differentiable in
    ``vals``, ``b``, ``bias`` and ``residual``, batched ``b (..., k, n) →
    (..., m, n)``.  With a uniform plan and a matching mesh this is one
    ``shard_map`` dispatch (each device runs its local planned kernel);
    otherwise a per-shard loop computes the same values on whatever
    devices hold the data.

    The epilogue applies *after* shard assembly — a row shard holds only a
    row slice of C (the bias/residual would need slicing), and a column
    shard holds a rank-``m`` *partial sum*, through which a nonlinear
    activation does not commute — so the shards run epilogue-free in
    ``acc_dtype`` and the single tail pass lands on the assembled C.
    """
    exec = coalesce_exec("execute_sharded", exec, impl=impl,
                         interpret=interpret, tk=tk)
    meta = plan.meta
    if vals.shape != (meta.nnz_pad,):
        raise ValueError(
            f"sharded plan expects the global vals of shape "
            f"({meta.nnz_pad},) for pattern {meta.shape}, got {vals.shape}")
    if b.ndim < 2 or b.shape[-2] != meta.k:
        raise ValueError(
            f"sharded plan expects B of shape (..., {meta.k}, n) for "
            f"pattern {meta.shape}, got {b.shape}")
    from repro.core.spmm import _resolve_exec
    exec = _resolve_exec("execute_sharded", meta.m, vals, b, exec,
                         bias, residual)
    ep = exec.epilogue
    # Shards emit acc-precision blocks/partials (a cols-dim psum must not
    # sum down-cast partials); the out_dtype cast waits for the tail.
    inner = dataclasses.replace(exec, epilogue=None,
                                out_dtype=exec.acc_dtype)
    mesh = meta.spmd_mesh()
    if _trace._enabled:
        path = "spmd" if mesh is not None else "loop"
        _sharded_execute.labels(path=path).inc()
        _trace.event("dispatch.sharded", cat="dispatch", path=path,
                     n_shards=meta.n_shards, dim=meta.dim,
                     uniform=meta.uniform, impl=exec.impl,
                     method=meta.method, n=int(b.shape[-1]),
                     acc_dtype=exec.acc_dtype, out_dtype=exec.out_dtype)
    out = _execute_spmd(plan, vals, b, inner, mesh) if mesh is not None \
        else _execute_loop(plan, vals, b, inner)
    if ep is not None:
        from repro.core.epilogue import apply_epilogue
        acc = jnp.dtype(exec.acc_dtype)
        out = apply_epilogue(
            out, ep,
            bias.astype(acc)[:, None] if ep.bias else None,
            residual if ep.residual else None)
    return out.astype(jnp.dtype(exec.out_dtype))


def _execute_loop(plan, vals, b, exec):
    """Per-shard execution: correct for any shard mix, any device count."""
    meta = plan.meta
    outs = []
    for i, (p, slot) in enumerate(zip(plan.shards, plan.vals_slots)):
        lb = _local_b(b, plan.b_rows[i]) if meta.dim == "cols" else b
        with scope("shard_vals"):
            lv = _local_vals(vals, slot)
        outs.append(execute_plan(p, lv, lb, exec))
    with scope("shard_concat"):
        if meta.dim == "rows":
            return _concat_rows(outs, meta.bounds)
        return sum(outs[1:], outs[0])


def _execute_spmd(plan, vals, b, exec, mesh):
    """One shard_map dispatch: every device runs its local planned kernel."""
    meta = plan.meta
    axis = meta.axis
    stacked_plan, slot_stack, brow_stack = plan._stacked()

    # Each device's block of a stacked leaf is its own shard's leaf.
    if meta.dim == "rows":
        def body(plan_s, slot_s, vals, b):
            with scope("shard_vals"):
                lv = _local_vals(vals, slot_s)
            return execute_plan(plan_s, lv, b, exec)[None]

        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=P(axis), check_vma=False,
        )(stacked_plan, slot_stack, vals, b)
        with scope("shard_concat"):
            return _concat_rows([out[i] for i in range(meta.n_shards)],
                                meta.bounds)

    def body(plan_s, slot_s, brow_s, vals, b):
        with scope("shard_vals"):
            lv = _local_vals(vals, slot_s)
        partial = execute_plan(plan_s, lv, _local_b(b, brow_s), exec)
        with scope("shard_concat"):
            return jax.lax.psum(partial, axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P()),
        out_specs=P(), check_vma=False,
    )(stacked_plan, slot_stack, brow_stack, vals, b)
