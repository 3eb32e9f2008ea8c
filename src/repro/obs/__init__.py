"""Engine-wide observability: tracing, metrics, device-op scopes.

* :mod:`repro.obs.trace` — scoped spans and instant events.  Every span
  enters a ``jax.profiler.TraceAnnotation`` whenever a profiler records,
  so any ``jax.profiler`` capture holds the program's spans on the host
  clock;
  the in-memory ring and its Chrome trace-event export stay behind
  ``REPRO_TRACE=1``, :func:`enable`, or ``with obs.tracing(): ...``.
* :mod:`repro.obs.metrics` — typed counter/gauge/histogram families with
  labels on the process-global :data:`registry`; ``report()`` /
  ``dump_metrics()`` expose them.
* :mod:`repro.obs.scopes` — the program's ``jax.named_scope`` vocabulary
  (:data:`SCOPES`, opened through :func:`scope`, always on) and
  :func:`device_scopes`, which maps a compiled program's device ops to
  it: the way to read a profiler capture by layer.
* :mod:`repro.obs.roofline` — compulsory-bytes models of SpMM, which the
  static traffic analyzer holds programs against.

This package imports only the stdlib at module load (jax is imported
lazily by profiler annotations, scopes and the HLO join), so core engine
modules may import it freely without cycles.
"""
from __future__ import annotations

from . import trace as trace
from .metrics import (Counter, Gauge, Histogram, MetricFamily,
                      MetricsRegistry)
from .roofline import (fused_epilogue_ceiling, plan_bwd_min_bytes,
                       plan_min_bytes, sddmm_min_bytes, spmm_flops,
                       spmm_min_bytes)
from .scopes import KERNELS, SCOPES, device_scopes, scope
from .trace import (Tracer, disable, enable, event, get_tracer, is_enabled,
                    span, tracing)

# Process-global registry: instrumentation sites across the engine share
# it without import-order coupling.
registry = MetricsRegistry()

__all__ = [
    "Counter", "Gauge", "Histogram", "KERNELS", "MetricFamily",
    "MetricsRegistry", "SCOPES", "Tracer", "device_scopes", "disable",
    "dump_metrics", "enable", "event", "fused_epilogue_ceiling",
    "get_tracer", "is_enabled", "plan_bwd_min_bytes", "plan_min_bytes",
    "registry", "report", "reset", "scope", "sddmm_min_bytes", "span",
    "spmm_flops", "spmm_min_bytes", "trace", "tracing",
]


def dump_metrics(path: str, *, extra: dict | None = None) -> str:
    """Write the global registry snapshot as JSON; returns the path."""
    return registry.dump(path, extra=extra)


def _rung_rates() -> dict[str, float]:
    """Ladder-rung hit rates from ``plan_resolve_total``, as fractions."""
    fam = registry.get("plan_resolve_total")
    if fam is None:
        return {}
    by_rung: dict[str, int] = {}
    for c in fam.children():
        rung = c.labels.get("rung", "?")
        by_rung[rung] = by_rung.get(rung, 0) + c.value
    total = sum(by_rung.values())
    if total == 0:
        return {}
    return {r: n / total for r, n in sorted(by_rung.items())}


def report() -> str:
    """Text snapshot of the whole subsystem: ladder-rung hit rates, the
    metrics exposition and the tracer's state."""
    parts = []
    rates = _rung_rates()
    if rates:
        parts.append("== resolution ladder ==")
        parts.append("  ".join(f"{r}={v * 100:.1f}%"
                               for r, v in rates.items()))
    m = registry.report()
    if m:
        parts.append("== metrics ==")
        parts.append(m)
    tr = get_tracer()
    if tr is not None:
        parts.append(f"== trace == {len(tr)} events buffered"
                     + (f" ({tr.dropped} dropped)" if tr.dropped else ""))
    return "\n".join(parts)


def reset() -> None:
    """Zero metrics; clear the tracer ring (tests)."""
    registry.reset()
    tr = get_tracer()
    if tr is not None:
        tr.clear()
