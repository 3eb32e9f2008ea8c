"""Reductions several metric readers share (``bench/metrics/*.py``)."""
from __future__ import annotations

from bench import work

# On a TPU a device operation's name is its HLO instruction: a Pallas
# kernel is a ``tpu_custom_call``, named after the program's jitted SpMM
# wrapper (``%rowsplit_execute.1 = ... custom-call(...)``).
PALLAS = 'custom_call_target="tpu_custom_call"'
SPMM_KERNELS = ("rowsplit", "merge", "rowgroup")


def is_spmm_kernel(op) -> bool:
    if PALLAS not in op.name:
        return False
    head = op.name.split(" = ", 1)[0]
    return any(k in head for k in SPMM_KERNELS)


def spmm_roofline(run):
    """Percent: least time of the window's SpMM calls on all the cell's
    chips together (one chip's least time over the chip count) over the
    SpMM kernel events' device time a chip; None without a trace or
    without kernel events."""
    calls = run.window.spmm
    if run.trace is None or run.peak is None or not calls:
        return None
    kernel_s = run.trace.op_seconds(is_spmm_kernel)
    if kernel_s <= 0:
        return None
    least = work.spmm_least_seconds(calls, run.peak) / run.chips
    return 100.0 * least / kernel_s


def idle_share(run):
    """Percent of the traced window with no device operation running."""
    if run.trace is None or run.trace.window_s <= 0 or \
            not any(run.trace.devices):
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
