"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one cell, configuration, traffic mix or
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

    bench/configs/<config>.json      sizes; ``family`` names the system
    bench/systems/<family>.py        builds the system under test from the
                                     program, drives its timed path, and
                                     compares with the reference
    bench/references/<family>.py     the plain reference (no program code)
    bench/traffic/<traffic>.json     the traffic mix: loop kind and sizes
    bench/limits/<cell>.json         limits that decide ``correct``
    bench/metrics/<metric>.py        ``read(run)`` -> number or None
    bench/peaks.json                 published peaks by ``device_kind``

A run: find the chips (no TPU, or fewer than the cell asks for: exit
non-zero with no result), set up (weights or graph from the seed, the
program's plans, compile, warm-up; ``setup_s`` counts from the moment
the chips are found), measure for ``--seconds`` under the traffic's
loop, read peak memory, free the program's state, compare the sampled
answers with the reference, and print the metrics of the cell's
``end_to_end`` (``--trace 0``) or ``per_layer`` (``--trace 1``) entries.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Where runs write what they leave behind (traces); listed in .gitignore.
OUT = ".bench_out"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# --------------------------------------------------------------- the spec ---


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: str


def resolve_cell(root: str, name: str) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    w = _by_name(spec["workloads"], name, "workload")
    c = _by_name(spec["configs"], w["config"], "config")
    bench = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, c["file"])),
        traffic=load_json(os.path.join(bench, "traffic",
                                       w["traffic"] + ".json")),
        limits=load_json(os.path.join(bench, "limits", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root)


def system_class(cell: Cell):
    family = cell.config["family"]
    mod = load_module(os.path.join(cell.root, "bench", "systems",
                                   family + ".py"), f"bench_sys_{family}")
    return mod.System


def metric_reader(root: str, name: str):
    mod = load_module(os.path.join(root, "bench", "metrics", name + ".py"),
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read


# ------------------------------------------------------------- the clock ---


class SetupClock:
    """Seconds spent in each named phase of set-up."""

    def __init__(self):
        self.phases: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + \
                time.perf_counter() - t0


class CompileCounter:
    """Counts programs JAX lowers (from any thread) while armed: every new
    program, whether its binary then comes from the persistent cache or a
    fresh compile, is lowered first."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _installed: "CompileCounter | None" = None

    def __init__(self):
        self.armed = False
        self.count = 0
        self._lock = threading.Lock()

    def _listen(self, event, duration, **kw):
        if event == self.EVENT and self.armed:
            with self._lock:
                self.count += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax.monitoring
            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed._listen)
        return cls._installed


# ------------------------------------------------------------- the loops ---


class Sampler:
    """The answers the check compares: a seeded reservoir of ``k`` of all
    answers offered, plus the one with the largest size."""

    def __init__(self, k: int, seed: int):
        from bench import traffic as T
        self.k = k
        self.rng = T.rng(seed, 5)
        self.seen = 0
        self.items: list = []
        self.largest = None          # (size, payload)

    def offer(self, size: int, payload) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(payload)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = payload
        if self.largest is None or size > self.largest[0]:
            self.largest = (size, payload)

    def sample(self) -> list:
        out = list(self.items)
        if self.largest is not None and not any(
                p is self.largest[1] for p in out):
            out.append(self.largest[1])
        return out


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    units: int = 0                # calls completed
    tokens: int | None = None     # useful tokens completed, where counted
    attempted: int = 0
    failed: int = 0
    flops: float = 0.0            # operations the completed work requires
    spmm: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


def _annotate(name):
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


def closed_batch(system, seconds: float, sampler: Sampler) -> Window:
    """One call at a time, each sent when the last has finished; every
    call's work and time counts, the last one ending after the close."""
    import jax
    tokens = 0
    with _annotate("bench.window"):
        w = Window(time.perf_counter(), 0.0)
        deadline = w.t0 + seconds
        i = 0
        while True:
            with _annotate("bench.dispatch"):
                out = system.call(i)
            with _annotate("bench.wait"):
                out = jax.block_until_ready(out)
            n = system.unit_tokens(i)
            wk = system.unit_work(i)
            w.flops += wk["flops"]
            w.spmm.extend(wk["spmm"])
            tokens += n or 0
            sampler.offer(n or 0, (i, out))
            del out
            i += 1
            if time.perf_counter() >= deadline:
                break
        w.t_end = time.perf_counter()
    w.units = w.attempted = i
    w.tokens = tokens if system.unit_tokens(0) is not None else None
    return w


LOOPS = {"closed_batch": closed_batch}


# --------------------------------------------------------------- the run ---


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    system: object
    peak: dict
    t_start: float                   # when the chips were found
    setup_phases: dict
    window: Window
    trace: object = None             # bench.trace_reduce.Trace

    @property
    def setup_s(self) -> float:
        return self.window.t0 - self.t_start


def chips(n: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}, "
                     f"kind {devs[0].device_kind!r}, {len(devs)} devices)")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)} "
                     f"({devs[0].platform} {devs[0].device_kind})")
    return devs[:n]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devs,
             t_process: float | None = None) -> tuple[dict, list]:
    """Set up, measure and check one cell.  Returns the result object and
    the checks (name, value, limit)."""
    import jax

    from bench import trace_reduce
    from bench.peaks import peak_for

    kind = devs[0].device_kind
    peak = peak_for(kind) if devs[0].platform == "tpu" else None
    clock = SetupClock()
    with clock.phase("import"):
        prepare_jax(cell.root)
        cls = system_class(cell)
        from repro import engine
    system = cls(cell.config, cell.traffic, seed, clock)
    system.setup()
    sampler = Sampler(int(cell.traffic["sample"]), seed)
    counter = CompileCounter.get()
    loop = LOOPS[cell.traffic["loop"]]
    trace_dir = os.path.join(cell.root, OUT, "trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    misses0 = engine.cache_stats().misses
    counter.count, counter.armed = 0, True
    try:
        window = loop(system, seconds, sampler)
    finally:
        counter.armed = False
        if trace:
            jax.profiler.stop_trace()
    compiles = counter.count
    misses = engine.cache_stats().misses - misses0
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devs)
    run = Run(cell=cell, system=system, peak=peak, t_start=t_start,
              setup_phases=dict(clock.phases), window=window)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if trace:
        run.trace = trace_reduce.load(trace_dir, len(devs))
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = metric_reader(cell.root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    samples = sampler.sample()
    system.release()
    numbers = system.check([p for p in samples])
    checks = [(k, numbers[k], float(cell.limits[k]["limit"]))
              for k in sorted(cell.limits)]
    checks += [("compiles_in_window", compiles, 0),
               ("plans_built_in_window", misses, 0),
               ("failed", window.failed, 0)]
    correct = all(v <= lim for _, v, lim in checks) and window.units > 0
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
    result["setup"] = {"setup_s": run.setup_s, **clock.phases}
    if t_process is not None:
        # Interpreter start to chips found: Python, JAX and the TPU
        # runtime starting, outside ``setup_s``.
        result["setup"]["start_s"] = t_start - t_process
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, v, lim in checks}
    return result, checks


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_jax(root: str) -> None:
    """The program on the path, and JAX's persistent compilation cache in
    the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    # Every program, however quick to compile, comes from the cache on
    # later runs: set-up then does the same work each time.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv, t_process: float | None = None, root: str = ROOT,
         require_tpu: bool = True) -> int:
    """One run.  ``setup_s`` counts from the moment the chips are found;
    ``t_process``, the interpreter's start, is only reported beside it."""
    args = parse_args(argv)
    cell = resolve_cell(root, args.workload)
    try:
        devs = chips(cell.chips, require_tpu)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start, devs, t_process)
    for k, v, lim in checks:
        print(f"[bench] check {k}: {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
