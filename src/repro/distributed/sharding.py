"""Sharding rules: parameter FSDP×TP, activation DP, cache layouts.

Scheme (MaxText-style 2D + optional pod axis):

* mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
  multi-pod.  ``dp`` below = ``("pod", "data")`` when the pod axis exists.
* params: FSDP-shard the *contracting-free* large dim over ``data`` and
  tensor-shard the other over ``model`` (XLA GSPMD inserts the FSDP
  all-gathers, overlapped with the layer scan, and the TP partial-sum
  all-reduces).
* every rule is divisibility-checked: an axis that does not divide the dim
  is dropped (e.g. granite's vocab 49155 over 16) — correctness first,
  the roofline shows the cost.
* batch:  ``(dp, None, ...)``;  KV caches: batch over ``dp`` when it
  divides, sequence over ``model`` (flash-decoding style), and over
  ``dp×model`` for the 500k single-sequence cell.
"""
from __future__ import annotations


import contextlib
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fit(mesh: Mesh, dim: int, axes):
    """Return axes if they divide dim, else progressively drop axes."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    while axes and dim % _axis_size(mesh, axes) != 0:
        axes = axes[:-1]
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _p(mesh, dims, *axes):
    """PartitionSpec with divisibility-checked axes per dim."""
    return P(*[_fit(mesh, d, a) for d, a in zip(dims, axes)])


# ----------------------------------------------------------- parameters ----

_ROW = object()   # shard dim over fsdp(data)
_COL = object()   # shard dim over model

_PARAM_RULES = {
    # name -> axes for the *last* ndims (leading scan dims -> None)
    "embed": ("data", "model"),
    "unembed": ("data", "model"),
    "router": ("data", None),
    "wq": ("data", "model"), "wk": ("data", "model"),
    "wv": ("data", "model"), "wo": ("model", "data"),
    "bq": ("model",), "bk": ("model",), "bv": ("model",),
    "w1": ("data", "model"), "w3": ("data", "model"),
    "w2": ("model", "data"),
    "in_proj": ("data", "model"), "out_proj": ("model", "data"),
    "conv": (None, "model"),
    "wx_in": ("data", "model"), "wg_in": ("data", "model"),
    "out": ("model", "data"),
    "gate_a": ("model",), "gate_x": ("model",), "lam": ("model",),
    "scale": (None,), "bias": (None,),
    "a_log": (None,), "d_skip": (None,), "dt_bias": (None,),
}

_MOE_3D = {"w1": (None, "data", "model"), "w3": (None, "data", "model"),
           "w2": (None, "model", "data")}


def param_pspec(path, leaf, mesh: Mesh, mode: str = "fsdp") -> P:
    """mode: "fsdp" (data-FSDP × model-TP), "zero1" (model-TP only —
    compute replica; master is FSDP inside the optimizer), "fsdp2"
    (pure ZeRO-3 over the flattened data×model axes, no TP), "tp"
    (serving: model-TP only, with the embedding's vocabulary rows over
    model too)."""
    name = None
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            name = str(k.key)
            break
    dims = leaf.shape
    if name not in _PARAM_RULES:
        return P()
    rules = _PARAM_RULES[name]
    # MoE expert weights have a trailing (E, d_in, d_out) signature
    if name in _MOE_3D and len(dims) >= 3 and name in ("w1", "w2", "w3"):
        # distinguish from stacked dense mlp (count, d, ff) by checking the
        # path for "moe"
        if any(isinstance(k, jax.tree_util.DictKey) and str(k.key) == "moe"
               for k in path):
            rules = _MOE_3D[name]
            # NOTE §Perf iteration 10: expert-parallel weight sharding
            # (E over data) was tested and REFUTED — GSPMD reshards the
            # token buffer to the expert layout at 9× the wire bytes.
    if mode == "zero1":       # compute replica: model axes only
        rules = tuple(None if r == "data" else r for r in rules)
    elif mode == "tp":        # + vocabulary-parallel embedding
        rules = ("model", None) if name in ("embed", "unembed") else \
            tuple(None if r == "data" else r for r in rules)
    elif mode == "fsdp2":     # ZeRO-3 over every device, no TP
        dpm = dp_axes(mesh) + ("model",)
        rules = tuple(dpm if r == "data" else None for r in rules)
    lead = len(dims) - len(rules)
    if lead < 0:  # unexpected rank; replicate
        return P()
    axes = (None,) * lead + tuple(rules)
    return _p(mesh, dims, *axes)


def params_shardings(params, mesh: Mesh, mode: str = "fsdp"):
    if mode is True:   # backwards compat: fsdp flag
        mode = "fsdp"
    elif mode is False:
        mode = "zero1"
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_pspec(path, leaf, mesh, mode)),
        params)


# ------------------------------------------------------------ batches ------


def batch_pspec(shape, mesh: Mesh, batch_axis: int = 0,
                include_model: bool = False) -> P:
    dp = dp_axes(mesh)
    if include_model:
        dp = dp + ("model",)
    axes = [None] * len(shape)
    axes[batch_axis] = dp
    return _p(mesh, shape, *axes)


def batch_shardings(batch, mesh: Mesh, batch_axis: int = 0,
                    include_model: bool = False):
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, batch_pspec(leaf.shape, mesh, batch_axis, include_model)),
        batch)


# ------------------------------------------------------------- caches ------


def cache_pspec(path, leaf, mesh: Mesh) -> P:
    """KV caches (b, S, kv, dh): batch over dp, seq over model; if the
    batch doesn't shard (e.g. b=1 at 500k), sequence takes dp too.
    Recurrent states (b, ...): batch over dp, widest trailing dim over
    model."""
    dims = leaf.shape
    name = None
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            name = str(k.key)
            break
    dp = dp_axes(mesh)
    if name in ("k", "v") and len(dims) == 5:   # (layers, b, S, kv, dh)
        b, s = dims[1], dims[2]
        if b % _axis_size(mesh, dp) == 0:
            return _p(mesh, dims, None, dp, "model", None, None)
        return _p(mesh, dims, None, None, dp + ("model",), None, None)
    if name == "ssm" and len(dims) == 5:        # (layers, b, H, P, N)
        return _p(mesh, dims, None, dp, "model", None, None)
    if name == "conv" and len(dims) == 4:       # (layers, b, w-1, c)
        return _p(mesh, dims, None, dp, None, "model")
    if name == "h" and len(dims) == 3:          # (layers, b, w)
        return _p(mesh, dims, None, dp, "model")
    # fallback: batch over dp on axis 1 (after layer-stack axis)
    axes = [None] * len(dims)
    if len(dims) >= 2:
        axes[1] = dp
    return _p(mesh, dims, *axes)


def cache_shardings(caches, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, cache_pspec(path, leaf, mesh)),
        caches)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ------------------------------------------- activation constraints --------
#
# GSPMD left alone will reshard activations inside scan bodies (per-chunk
# collective-permutes / all-gathers — see EXPERIMENTS.md §Perf iteration 0).
# The model code pins the layouts it wants through ``constrain``, which is a
# no-op unless a launcher activates a mesh via ``use_mesh`` (CPU unit tests
# run unconstrained).

_TLS = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate activation-sharding constraints for model code."""
    prev = getattr(_TLS, "mesh", None)
    _TLS.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _TLS.mesh = prev


def active_mesh() -> Mesh | None:
    return getattr(_TLS, "mesh", None)


def constrain(x, *axes):
    """with_sharding_constraint(x, P(axes…)) under the active mesh.

    Axis entries: ``"dp"`` → the data(+pod) axes, ``"dpm"`` → data(+pod)
    +model flattened (pure-FSDP mode), ``"model"``, ``None``.
    Divisibility-checked like every other rule; identity when no mesh is
    active."""
    mesh = active_mesh()
    if mesh is None:
        return x
    def resolve(a):
        if a == "dp":
            return dp_axes(mesh)
        if a == "dpm":
            return dp_axes(mesh) + ("model",)
        return a
    named = [resolve(a) for a in axes]
    spec = _p(mesh, x.shape, *named)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
