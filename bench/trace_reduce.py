"""From a profiler trace to the numbers the per-layer metrics read.

A traced run wraps its measured window in ``jax.profiler`` and marks it
with a host annotation (``bench.window``); the harness's other spans
(``bench.*``) are host annotations too.
This module reads the ``.xplane.pb`` the profiler writes, with nothing but
JAX, and keeps:

* the device operations of each chip (the ``XLA Ops`` line of each
  ``/device:`` plane), clipped to the window;
* the host annotations whose names start with one of ``HOST_PREFIXES``.

The device planes keep their own clock.  Each program execution carries
a ``run_id`` on the device (``XLA Modules``) and on the host (enqueued in
``DoEnqueueProgram``, completed in ``CompleteCallbacks``), so the device
clock is moved onto the host's by the offset those pairs bound: no
execution starts before it was enqueued or ends after its completion was
reported.

Busy time is the union of a chip's operation intervals in the window;
idle gaps are the holes in that union, each named after the innermost
host annotation that covers its midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

HOST_PREFIXES = ("bench.",)
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUED = "DoEnqueueProgram"
COMPLETED = "CompleteCallbacks"


@dataclasses.dataclass
class Op:
    name: str
    start: float            # ns, on the trace's clock
    end: float
    stats: dict


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]
    devices: list[list[Op]]          # one list per chip, sorted by start
    host: list[Op]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clip(self, ops):
        lo, hi = self.window
        for op in ops:
            s, e = max(op.start, lo), min(op.end, hi)
            if e > s:
                yield op, s, e

    def busy_intervals(self, dev: int) -> list[tuple[float, float]]:
        """Union of one chip's operation intervals inside the window."""
        out: list[list[float]] = []
        for _, s, e in self._clip(self.devices[dev]):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        per = [sum(e - s for s, e in self.busy_intervals(d))
               for d in range(len(self.devices))]
        return sum(per) / len(per) / 1e9

    def op_seconds(self, match) -> float:
        """Summed durations of the operations ``match(op)`` accepts,
        averaged over the chips."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for ops in self.devices
                  for op, s, e in self._clip(ops) if match(op))
        return tot / len(self.devices) / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """The device operations that took most time (first chip), summed
        by kind: the HLO instruction's name without its numeric suffix
        (``%rowsplit_execute.11 = ...`` counts as ``rowsplit_execute``)."""
        tot: dict[str, float] = collections.defaultdict(float)
        for op, s, e in self._clip(self.devices[0] if self.devices else []):
            tot[op_kind(op.name)] += (e - s) / 1e9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest idle time on the first chip, summed by the host
        annotation that was open when the device went idle."""
        if not self.devices:
            return []
        lo, hi = self.window
        busy = self.busy_intervals(0)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        tot: dict[str, float] = collections.defaultdict(float)
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                tot[self.host_label((s + e) / 2)] += (e - s) / 1e9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def host_label(self, t: float) -> str:
        """Name of the shortest host annotation covering ``t`` (the
        innermost), other than the window itself."""
        best = None
        for sp in self.host:
            if sp.name != WINDOW and sp.start <= t <= sp.end and (
                    best is None or sp.end - sp.start < best.end - best.start):
                best = sp
        return best.name if best is not None else "host outside any span"


def op_kind(name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, dot, suffix = head.rpartition(".")
    return base if dot and suffix.isdigit() else head


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        if isinstance(v, (str, int, float)):
            out[k] = v
    return out


def xplane_path(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def clock_offset(runs: dict, enqueued: dict, completed: dict) -> float:
    """Nanoseconds to add to device times to put them on the host clock:
    the middle of the interval that every matched execution allows.
    ``runs`` maps run_id to the device (start, end); the others map run_id
    to host times."""
    lo = [enqueued[r] - s for r, (s, _) in runs.items() if r in enqueued]
    hi = [completed[r] - e for r, (_, e) in runs.items() if r in completed]
    if lo and hi:
        return (max(lo) + min(hi)) / 2
    return max(lo) if lo else (min(hi) if hi else 0.0)


def load(log_dir: str, n_devices: int) -> Trace:
    """Read the newest trace under ``log_dir``; keep ``n_devices`` chips,
    their times moved onto the host clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path(log_dir))
    devices: list[tuple[list[Op], dict]] = []
    host: list[Op] = []
    enqueued: dict = {}
    completed: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, runs = [], {}
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    ops = [Op(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns, _stats(ev))
                           for ev in ln.events]
                elif ln.name == MODULES_LINE:
                    for ev in ln.events:
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            runs[str(rid)] = (ev.start_ns,
                                              ev.start_ns + ev.duration_ns)
            devices.append((ops, runs))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append(Op(ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns, {}))
                    elif ev.name in (ENQUEUED, COMPLETED):
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            book = enqueued if ev.name == ENQUEUED \
                                else completed
                            book[str(rid)] = ev.start_ns
    chips = []
    for ops, runs in devices[:n_devices]:
        d = clock_offset(runs, enqueued, completed)
        chips.append(sorted((Op(o.name, o.start + d, o.end + d, o.stats)
                             for o in ops), key=lambda o: o.start))
    wins = [sp for sp in host if sp.name == WINDOW]
    if wins:
        window = (wins[0].start, wins[0].end)
    else:
        starts = [o.start for ops in chips for o in ops]
        ends = [o.end for ops in chips for o in ops]
        window = (min(starts, default=0.0), max(ends, default=0.0))
    return Trace(window=window, devices=chips, host=host)
