"""Structured tracing: scoped spans + instant events in a ring buffer.

Every span enters a ``jax.profiler.TraceAnnotation`` for its duration,
whether or not the ring is on: the annotation checks whether a profiler
is recording and otherwise does nothing, so
any ``jax.profiler`` capture — the benchmark's ``--trace 1`` or an
operator's — holds the program's spans on the host clock the device ops
are moved onto.  Device ops are read by layer through the program's
always-on ``jax.named_scope`` vocabulary (:mod:`repro.obs.scopes`,
:func:`repro.obs.device_scopes`).

The in-memory ring is gated: every instrumentation site guards on the
module-level ``_enabled`` flag (one attribute read), so the engine's warm
execute path records nothing while it is off.  When enabled
(``REPRO_TRACE=1`` in the environment, :func:`enable`, or the
:func:`tracing` context manager), spans land in a bounded ring buffer as
Chrome trace-event records — exportable with :meth:`Tracer.export` and
viewable in Perfetto / ``chrome://tracing``.

The emitting sites (see ``core/config.py``, ``core/plan.py``,
``engine/cache.py``, ``core/spmm.py``, ``distributed/spmm.py``) use four
categories:

* ``plan``     — ``PlanPolicy.resolve`` (which ladder rung fired),
  ``plan.build``, the build steps (``plan.step.fingerprint`` and
  ``plan.step.resolve`` before the build, ``plan.step.structure`` and
  ``plan.step.row_ids`` inside it), sharded plan assembly,
* ``cache``    — plan-cache hit / miss / eviction,
* ``dispatch`` — kernel dispatch (method, impl, dtypes, epilogue, tk),
* ``serve`` / ``train`` — launcher request/step scopes.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

DEFAULT_CAPACITY = 65536

# Fast-path flag: instrumentation sites read this attribute directly.
_enabled: bool = False
_tracer: "Tracer" | None = None
_lock = threading.Lock()


def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


class Tracer:
    """Bounded ring buffer of Chrome trace events (thread-safe appends).

    Events are dicts in the Chrome trace-event format: complete spans
    (``ph="X"`` with ``ts``/``dur`` in µs) and instant events
    (``ph="i"``).  The ring (``capacity`` events) keeps a long traced
    serving session bounded: old events fall off the front.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        self._elock = threading.Lock()
        self._pid = os.getpid()
        self.dropped = 0

    def record(self, ev: dict) -> None:
        with self._elock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)

    def add_complete(self, name: str, cat: str, ts_us: float, dur_us: float,
                     args: dict) -> None:
        self.record({"name": name, "cat": cat or "default", "ph": "X",
                     "ts": ts_us, "dur": dur_us, "pid": self._pid,
                     "tid": threading.get_ident(), "args": args})

    def add_instant(self, name: str, cat: str, args: dict) -> None:
        self.record({"name": name, "cat": cat or "default", "ph": "i",
                     "ts": _now_us(), "pid": self._pid,
                     "tid": threading.get_ident(), "s": "t", "args": args})

    def events(self, *, cat: str | None = None,
               name: str | None = None) -> list:
        """Snapshot of the ring, optionally filtered by category/name."""
        with self._elock:
            evs = list(self._events)
        if cat is not None:
            evs = [e for e in evs if e.get("cat") == cat]
        if name is not None:
            evs = [e for e in evs if e.get("name") == name]
        return evs

    def clear(self) -> None:
        with self._elock:
            self._events.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._elock:
            return len(self._events)

    def chrome_trace(self) -> dict:
        """The ring as a Chrome trace-event JSON object."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms",
                "otherData": {"producer": "repro.obs",
                              "dropped_events": self.dropped}}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path`` (returns the path)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# ------------------------------------------------------------ span scopes ---


_profiler = None


def _trace_me():
    """JAX's ``TraceAnnotation`` class (a profiler ``TraceMe``), or False
    where jax cannot be imported."""
    global _profiler
    if _profiler is None:
        try:
            import jax.profiler
            _profiler = jax.profiler.TraceAnnotation
        except Exception:
            _profiler = False
    return _profiler


class _Span:
    """A live span: a profiler annotation for its duration (while a
    profiler records) and, when the ring is on, a complete ("X") event on
    exit.

    ``set(**kw)`` adds args after entry (e.g. the resolution rung, known
    only mid-body).
    """

    __slots__ = ("name", "cat", "args", "_t0", "_ann")

    def __init__(self, name: str, cat: str, args: dict, ann):
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = None
        self._ann = ann

    def set(self, **kw) -> None:
        self.args.update(kw)

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        if _enabled:
            self._t0 = _now_us()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = _tracer
        if self._t0 is not None and tr is not None:
            tr.add_complete(self.name, self.cat, self._t0,
                            _now_us() - self._t0, self.args)


class _NullSpan:
    """Neither ring nor profiler: a shared, do-nothing context manager."""

    __slots__ = ()

    def set(self, **kw) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


def span(name: str, cat: str = "", **args):
    """A scoped span — ``with obs.span("plan.build", cat="plan", ...):``.

    A profiler annotation whenever a ``jax.profiler`` capture is
    recording, whether or not tracing is enabled; recorded in the ring
    (with ``args``) while tracing is enabled.  With neither, the shared
    null span, after one profiler check."""
    tm = _trace_me()
    ann = tm(name) if tm and tm.is_enabled() else None
    if ann is None and not _enabled:
        return _NULL_SPAN
    return _Span(name, cat, args, ann)


def event(name: str, cat: str = "", **args) -> None:
    """An instant event (no duration). No-op when tracing is disabled."""
    if not _enabled:
        return
    tr = _tracer
    if tr is not None:
        tr.add_instant(name, cat, args)


# ------------------------------------------------------------- lifecycle ---


def is_enabled() -> bool:
    return _enabled


def get_tracer() -> Tracer | None:
    """The active Tracer, or None when tracing is disabled."""
    return _tracer


def enable(capacity: int = DEFAULT_CAPACITY) -> Tracer:
    """Turn tracing on (idempotent); returns the active Tracer."""
    global _enabled, _tracer
    with _lock:
        if _tracer is None:
            _tracer = Tracer(capacity)
        _enabled = True
        return _tracer


def disable() -> None:
    """Turn tracing off. The tracer (and its events) stay readable."""
    global _enabled
    with _lock:
        _enabled = False


class _Tracing:
    """``with obs.tracing() as tracer:`` — scoped enable/restore."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._prev: tuple | None = None

    def __enter__(self) -> Tracer:
        global _enabled, _tracer
        with _lock:
            self._prev = (_enabled, _tracer)
            _tracer = Tracer(self.capacity)
            _enabled = True
            return _tracer

    def __exit__(self, *exc) -> None:
        global _enabled, _tracer
        with _lock:
            _enabled, _tracer = self._prev


def tracing(capacity: int = DEFAULT_CAPACITY) -> _Tracing:
    """Context manager: enable tracing with a fresh Tracer, restore the
    previous state (including a previously active tracer) on exit."""
    return _Tracing(capacity)


# REPRO_TRACE=1 (any non-empty value except "0") enables tracing at import
# — the launcher-facing switch; make trace-smoke uses it.
if os.environ.get("REPRO_TRACE", "") not in ("", "0"):
    enable()
