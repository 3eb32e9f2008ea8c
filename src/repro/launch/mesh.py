"""Production mesh builders.

A FUNCTION (not module-level constant) so importing never touches jax
device state.  Single pod: 16×16 = 256 chips (v5e pod).  Multi-pod: 2 pods
= 512 chips with the "pod" axis outermost (data-parallel across pods over
DCN; hot-spare-pod swap happens at this axis, see distributed/fault.py).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with all-auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever this host has — used by examples/tests (1 CPU device)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
