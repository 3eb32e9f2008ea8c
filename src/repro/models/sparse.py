"""Pruned-FFN layers via SpMM — the paper's motivating use case (§1, [1]).

``SparseLinear`` stores a magnitude-pruned weight matrix in CSR and runs
the matmul through the plan-once/execute-many engine: the forward is
``y = (W_csr @ x.T).T`` where the activation matrix ``x.T (d_in, tokens)``
is the tall-skinny dense B — during decode ``tokens`` is the batch size
(1–128), exactly the paper's n ∈ [32, 128] regime.

Every pattern-derived static decision — kernel choice (§5.4 heuristic),
row-split ``l_pad``, chunk layout, and the transpose plan for the backward
pass — lives in the layer's ``SpmmPlan``, built once per sparsity pattern
through ``repro.engine``'s cache.  The layer is a pytree, so it passes
through ``jax.jit`` / ``jax.grad`` boundaries with its plan attached and
*never replans inside a jitted step*.  It is differentiable: gradients
flow to the CSR values (sparse fine-tuning of a pruned weight) and to the
activations.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CSR, ExecutionConfig, Heuristic, PlanPolicy,
                        SparseMatrix, SpmmPlan)
from repro.core.config import _UNSET, _warn_deprecated
from repro.obs import scope

# Below this many tokens per call, flattening the leading axes packs the
# tokens densely into the kernels' TN=128-lane tiles; from here up each
# batch element already fills its lane tiles, so the batched grid path —
# B (..., d_in, tokens) folded into the kernel's leading batch axis — wins
# by skipping the (batch*tokens) reshape/transpose and running the whole
# stack in one dispatch.
BATCHED_MIN_TOKENS = 128


def _legacy_heuristic(context: str, heuristic, policy):
    """Fold the pre-v1 ``heuristic=`` kwarg into a policy (warn once)."""
    if heuristic is _UNSET:
        return policy
    if policy is not None:
        raise ValueError(f"{context}: pass either policy= or the legacy "
                         "heuristic=, not both")
    _warn_deprecated(
        f"{context}(heuristic=...)",
        "pass policy=PlanPolicy(heuristic=...) "
        "(see README.md: Migrating to API v1)", stacklevel=4)
    return PlanPolicy(heuristic=heuristic)


@dataclasses.dataclass(frozen=True)
class SparseLinear:
    weight: CSR                    # (d_out, d_in)
    plan: SpmmPlan | None       # pattern plan (None = plan on first use)

    @classmethod
    def from_dense(cls, w: jax.Array, keep_fraction: float,
                   heuristic: Heuristic | None = _UNSET, *,
                   policy: PlanPolicy | None = None) -> "SparseLinear":
        """Prune w (d_in, d_out) — stored transposed as (d_out, d_in).

        ``policy`` pins the plan request (method, static params, TuneDB);
        the default lets the engine resolve the kernel method through the
        full ladder — TuneDB exact/class hits, then a DB-calibrated
        threshold — instead of pinning the analytic default.
        (``heuristic`` is the pre-v1 spelling of
        ``policy=PlanPolicy(heuristic=...)``; it warns once.)
        """
        policy = _legacy_heuristic("SparseLinear.from_dense", heuristic,
                                   policy)
        if policy is None:
            policy = PlanPolicy()
        mtx = SparseMatrix.prune(np.asarray(w).T, keep_fraction, policy)
        return cls(mtx.data, mtx.spmm_plan)

    @property
    def matrix(self) -> SparseMatrix:
        """This layer's weight as the v1 ``SparseMatrix`` frontend."""
        return SparseMatrix(self.weight, self.plan)

    def with_plan(self, heuristic: Heuristic | None = _UNSET, *,
                  policy: PlanPolicy | None = None) -> "SparseLinear":
        """(Re)attach the engine-cached plan for this weight's pattern.

        Identity-cheap when the plan is already cached — use after
        checkpoint restore or pattern surgery, outside jit.
        """
        policy = _legacy_heuristic("SparseLinear.with_plan", heuristic,
                                   policy)
        if policy is None and self.plan is not None:
            # Replay the existing plan's full statics (method and tuned
            # t/tl/l_pad), not just its method — a TuneDB-tuned l_pad
            # must survive a re-plan after checkpoint restore.  After
            # pattern surgery that outgrows a pattern-derived parameter,
            # plan_like falls back to the method alone and re-derives.
            mtx = SparseMatrix(self.weight).plan_like(self.plan.meta)
        else:
            mtx = SparseMatrix(self.weight).plan(policy or PlanPolicy())
        return dataclasses.replace(self, plan=mtx.spmm_plan)

    def shard(self, mesh=None, *, n: int | None = None,
              dim: str = "rows", axis: str | None = None,
              policy: PlanPolicy | None = None) -> "SparseLinear":
        """Re-plan this layer's weight with a device-sharded plan.

        nnz-balanced shards, one local plan per shard, executed under
        ``shard_map`` when ``mesh`` is given and the shards are uniform —
        see ``SparseMatrix.shard`` / ``repro.distributed.spmm``.
        """
        mtx = SparseMatrix(self.weight).shard(mesh, n=n, dim=dim, axis=axis,
                                              policy=policy)
        return dataclasses.replace(self, plan=mtx.spmm_plan)

    @property
    def method(self) -> str:
        return self.plan.meta.method if self.plan is not None else "auto"

    @property
    def l_pad(self) -> int | None:
        return self.plan.meta.l_pad if self.plan is not None else None

    def __call__(self, x: jax.Array,
                 exec: ExecutionConfig | None = None, *,
                 bias: jax.Array | None = None,
                 residual: jax.Array | None = None, **kw) -> jax.Array:
        """x (..., d_in) → (..., d_out).  Differentiable in x and vals.

        ``exec`` is the per-call :class:`ExecutionConfig` (bare
        ``impl``/``interpret``/``tk`` kwargs fold into one through the
        ``execute_plan`` shims).  With 3-D+ activations carrying enough
        tokens per call (``BATCHED_MIN_TOKENS``), the leading axes ride
        the engine's batched execution — B (..., d_in, tokens) folds into
        the kernel grid — instead of being flattened into one wide token
        axis.

        ``bias (d_out,)`` / ``residual (..., d_out)`` (layer coordinates,
        like ``x``) and any ``exec.epilogue`` activation fuse into the
        SpMM's output write: the layer runs as ``y = (W @ xᵀ)ᵀ``, so the
        per-``d_out`` bias is exactly the kernel's per-C-row bias and the
        residual rides transposed into kernel coordinates.
        """
        layer = self if self.plan is not None else self.with_plan()
        mtx = layer.matrix
        w = layer.weight
        out_dtype = x.dtype if exec is None or exec.out_dtype is None \
            else jnp.dtype(exec.out_dtype)
        # The layout around the product belongs to the SpMM call's scopes:
        # B laid out and widened in, C laid back out.
        with scope("spmm"):
            if x.ndim >= 3 and x.shape[-2] >= BATCHED_MIN_TOKENS:
                with scope("widen_b"):                # (..., d_in, tok)
                    xt = jnp.swapaxes(x, -1, -2).astype(w.dtype)
                with scope("pad"):
                    res = None if residual is None else \
                        jnp.swapaxes(residual, -1, -2)
                y = mtx.matmul(xt, exec, bias=bias, residual=res, **kw)
                with scope("unpad"):
                    return jnp.swapaxes(y, -1, -2).astype(out_dtype)
            lead = x.shape[:-1]
            with scope("widen_b"):                    # (d_in, tokens) = B
                xt = x.reshape(-1, x.shape[-1]).T.astype(w.dtype)
            with scope("pad"):                        # (d_out, tokens) = C
                res = None if residual is None else \
                    residual.reshape(-1, w.m).T
            y = mtx.matmul(xt, exec, bias=bias, residual=res, **kw)
            with scope("unpad"):
                return y.T.reshape(*lead, w.m).astype(out_dtype)


jax.tree_util.register_pytree_node(
    SparseLinear,
    lambda sl: ((sl.weight, sl.plan), ()),
    lambda aux, ch: SparseLinear(*ch),
)


def prune_mlp(mlp_params: dict, keep_fraction: float,
              policy: PlanPolicy | None = None) -> dict:
    """Convert a dense MLP param dict (w1/w2[/w3]) to SparseLinear layers.

    ``policy`` pins every layer's plan request (e.g.
    ``PlanPolicy(method="rowgroup")`` from ``serve --spmm-method``).
    Plans come from the engine cache, so repeated pruning with the same
    masks (e.g. rebuilding layers each serving epoch) replans nothing.
    """
    return {name: SparseLinear.from_dense(w, keep_fraction, policy=policy)
            for name, w in mlp_params.items()}


def sparse_mlp_apply(sparse_p: dict, x: jax.Array, cfg,
                     exec: ExecutionConfig | None = None) -> jax.Array:
    """Apply a pruned MLP block (gelu or swiglu, by the param dict's keys).

    The gelu variant fuses the activation into w1's SpMM epilogue — C is
    written once, activated, instead of written and re-read by a separate
    elementwise program.  swiglu stays unfused: silu and the w3 gate are
    not epilogue shapes.  ``exec`` carries the per-call backend knobs for
    every layer; its ``epilogue`` field is overridden on w1 by the fused
    activation.
    """
    from repro.core.epilogue import Epilogue
    base = exec if exec is not None else ExecutionConfig()
    if "w3" in sparse_p:
        h = jax.nn.silu(sparse_p["w1"](x, base)) * sparse_p["w3"](x, base)
    else:
        fused = dataclasses.replace(base,
                                    epilogue=Epilogue(activation="gelu"))
        h = sparse_p["w1"](x, fused)
    return sparse_p["w2"](h, base)


def mlp_vals(sparse_p: dict) -> dict:
    """Extract the trainable CSR values of a SparseLinear dict."""
    return {name: sl.weight.vals for name, sl in sparse_p.items()}


def mlp_with_vals(sparse_p: dict, vals: dict) -> dict:
    """Rebind CSR values onto the (frozen-pattern) layers — the sparse
    fine-tuning parameterization: patterns and plans stay put, values are
    the optimizer's degrees of freedom."""
    return {name: dataclasses.replace(
        sl, weight=dataclasses.replace(sl.weight, vals=vals[name]))
        for name, sl in sparse_p.items()}
