"""Device time by program layer: the window's device operations joined
with the program's own map from instruction to scope.

The trace names each device operation by its HLO instruction alone
(``%fusion.12 = ...``); ``repro.obs.device_scopes`` of the compiled
program the window ran maps each instruction name to the innermost scope
of the program's vocabulary (``repro.obs.SCOPES``).  A program without
that map (one older than it) gives nothing, and the metrics that read
this are then left out of the result line.
"""
from __future__ import annotations

import collections

# Parts of an SpMM call around its kernel.
WRAPPER = ("gather_vals", "widen_b", "pad", "unpad")
# The model step outside SpMM (``mlp`` as innermost: the MLP's own ops).
DENSE = ("embed", "attn", "norm", "mlp", "unembed")


def instruction(op_name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def seconds_by_scope(run) -> dict | None:
    """Device seconds in the window by scope (``None`` for operations the
    map does not name), averaged over the chips; None without a trace or
    without the program's map."""
    compiled = getattr(run.system, "compiled", None)
    if run.trace is None or not any(run.trace.devices) or compiled is None:
        return None
    try:
        from repro.obs import device_scopes
    except ImportError:
        return None
    scopes = device_scopes(compiled)
    lo, hi = run.trace.window
    tot: dict = collections.defaultdict(float)
    for ops in run.trace.devices:
        for op in ops:
            s, e = max(op.start, lo), min(op.end, hi)
            if e > s:
                tot[scopes.get(instruction(op.name))] += (e - s) / 1e9
    return {k: v / len(run.trace.devices) for k, v in tot.items()}


def ms_per_call(run, names) -> float | None:
    """Device milliseconds a completed call spent in the scopes ``names``."""
    by = seconds_by_scope(run)
    if by is None or not run.window.units:
        return None
    return 1e3 * sum(by.get(k, 0.0) for k in names) / run.window.units
