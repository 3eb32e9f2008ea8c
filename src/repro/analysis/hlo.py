"""Trip-count-aware HLO module analysis for the roofline.

``compiled.cost_analysis()`` counts each while-loop *body once* — useless
for scan-over-layers graphs (80× undercount).  This parser walks the
post-SPMD HLO text, builds a per-computation symbol table, and accumulates

* **flops** — from ``dot`` ops: 2 × result_elements × contracted_size
  (matmul-dominated workloads; fusion-internal elementwise flops are
  ignored and noted in EXPERIMENTS.md),
* **hbm bytes** — fusion-level traffic model: every top-level instruction
  reads its operands and writes its result (gather/dynamic-slice count
  2×result — index-driven reads; updates count 2×update — in-place),
* **collective wire bytes** — ring-algorithm per-device wire cost of every
  all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute,

each scaled by the enclosing while-loops' trip counts (parsed from the loop
condition's bound constant — JAX counted loops start at 0).

Grown out of ``repro.launch.hlo_stats`` (which remains as a re-export
shim): this is the shared backend of the dry-run validator
(``repro.launch.dryrun``), the device-op scope join
(``repro.obs.device_scopes``) and the static bytes-moved analyzer
(``repro.analysis.traffic``).
``parse_compiled(..., detail=True)`` additionally reports the
multi-computation breakdown and the fusion/op histogram so hidden
copies and transposes are attributable to a computation.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
    "s4": 1, "u4": 1,
}

_ARRAY_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
# A TPU layout carries tiling and memory space: ``f32[8]{0:T(1024)S(1)}``.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(.*?\)|[\w\[\],{}:()]+)\s+"
    r"([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "conditional", "call", "after-all",
               "iota", "broadcast"}


def _type_bytes(t: str) -> int:
    return sum(_el_count(dims) * _DTYPE_BYTES[dt]
               for dt, dims in _ARRAY_RE.findall(t))


def _el_count(dims: str) -> int:
    if not dims:
        return 1
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n


def _wire_factor(op: str, g: int) -> float:
    if g <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (g - 1) / g
    if op in ("all-gather", "all-to-all"):
        return (g - 1) / g
    if op == "reduce-scatter":
        return float(g - 1)          # result = operand / g
    return 1.0                        # collective-permute


@dataclass
class CompStats:
    flops: float = 0.0
    bytes: float = 0.0
    coll_wire: float = 0.0
    coll_count: int = 0
    coll_by_op: dict = field(default_factory=lambda: defaultdict(float))
    # (callee, multiplier) pairs: while bodies × trip count, calls × 1
    calls: list = field(default_factory=list)


def _split_computations(text: str):
    comps, name, lines = {}, None, []
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            name, lines = m.group(1), []
            comps[name] = lines
        elif line.startswith("}"):
            name = None
        elif name is not None:
            lines.append(line)
    return comps


def _entry_name(text: str):
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
    return m.group(1) if m else None


def _trip_count(cond_lines) -> int:
    # JAX counted loops: cond compares the (0-initialised) counter with the
    # bound constant; take the max integer constant in the condition.
    best = 1
    for line in cond_lines:
        for c in _CONST_RE.findall(line):
            best = max(best, int(c))
    return best


def parse_module(text: str, *, detail: bool = False) -> dict:
    """Traffic model of one HLO module (multi-computation, trip-scaled).

    ``detail=True`` adds ``"computations"`` — each computation's *own*
    (unscaled, pre-call-graph) {flops, hbm_bytes} so hot loop bodies and
    hidden copy computations are attributable — and ``"fusion_ops"``,
    the module-wide op histogram of :func:`fusion_stats`.
    """
    comps = _split_computations(text)
    entry = _entry_name(text)
    stats: dict[str, CompStats] = {}

    for cname, lines in comps.items():
        cs = CompStats()
        symbols: dict[str, str] = {}
        for line in lines:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            iname, itype, op, rest = m.groups()
            symbols[iname] = itype
            if op.endswith("-start"):
                op = op[:-6]
            if op.endswith("-done"):
                continue  # counted at -start
            operands = re.findall(r"%([\w.\-]+)", rest.split("),")[0]
                                  if ")," in rest else rest)

            if op == "dot":
                cdims = _CDIMS_RE.search(rest)
                lhs_t = symbols.get(operands[0] if operands else "", "")
                arr = _ARRAY_RE.search(lhs_t or "")
                contracted = 1
                if cdims and arr:
                    dims = [int(d) for d in arr.group(2).split(",") if d]
                    for ci in cdims.group(1).split(","):
                        if ci:
                            contracted *= dims[int(ci)]
                cs.flops += 2.0 * _el_count(
                    _ARRAY_RE.search(itype).group(2)) * contracted

            if op in _COLLECTIVES:
                rb = _type_bytes(itype)
                g = 1
                mg = _GROUPS_RE.search(rest)
                if mg:
                    g = len(mg.group(1).split(","))
                else:
                    mg = _GROUPS_IOTA_RE.search(rest)
                    if mg:
                        g = int(mg.group(2))
                cs.coll_wire += rb * _wire_factor(op, g)
                cs.coll_count += 1
                cs.coll_by_op[op] += rb * _wire_factor(op, g)

            if op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", rest)
                mc = re.search(r"condition=%?([\w.\-]+)", rest)
                trips = _trip_count(comps.get(mc.group(1), [])) if mc else 1
                if mb:
                    cs.calls.append((mb.group(1), trips))
            elif op in ("call", "fusion"):
                # fusion bodies don't touch HBM; call bodies do (count ×1)
                if op == "call":
                    mt = re.search(r"to_apply=%?([\w.\-]+)", rest)
                    if mt:
                        cs.calls.append((mt.group(1), 1))
            elif op == "conditional":
                for mt in re.finditer(
                        r"(?:branch_computations=\{|true_computation=|"
                        r"false_computation=)%?([\w.\-]+)", rest):
                    cs.calls.append((mt.group(1), 1))

            if op not in _SKIP_BYTES:
                rb = _type_bytes(itype)
                if op in ("gather", "dynamic-slice"):
                    cs.bytes += 2.0 * rb
                elif op in ("scatter", "dynamic-update-slice"):
                    upd = (symbols.get(operands[1], "")
                           if len(operands) > 1 else itype)
                    cs.bytes += 2.0 * _type_bytes(upd)
                else:
                    ob = sum(_type_bytes(symbols.get(o, ""))
                             for o in operands)
                    cs.bytes += rb + ob
        stats[cname] = cs

    # accumulate from entry through the call graph with multipliers
    memo: dict[str, tuple] = {}

    def total(cname: str):
        if cname in memo:
            return memo[cname]
        cs = stats.get(cname)
        if cs is None:
            return (0.0, 0.0, 0.0, 0, {})
        f, b, w, n = cs.flops, cs.bytes, cs.coll_wire, cs.coll_count
        by = dict(cs.coll_by_op)
        memo[cname] = (f, b, w, n, by)  # break cycles defensively
        for callee, mult in cs.calls:
            cf, cb, cw, cn, cby = total(callee)
            f += cf * mult
            b += cb * mult
            w += cw * mult
            n += cn * mult
            for k, v in cby.items():
                by[k] = by.get(k, 0.0) + v * mult
        memo[cname] = (f, b, w, n, by)
        return memo[cname]

    f, b, w, n, by = total(entry) if entry else (0, 0, 0, 0, {})
    out = {
        "flops": f,
        "hbm_bytes": b,
        "collective_wire_bytes": w,
        "collective_count": n,
        "collective_by_op": by,
    }
    if detail:
        out["computations"] = {
            cname: {"flops": cs.flops, "hbm_bytes": cs.bytes}
            for cname, cs in stats.items()
            if cs.flops or cs.bytes or cs.calls}
        out["fusion_ops"] = fusion_stats(text)
    return out


def parse_compiled(fn, *args, detail: bool = False, **kwargs) -> dict:
    """``parse_module`` of a callable's compiled (post-SPMD) HLO.

    ``fn`` is jit-wrapped if it isn't already; ``*args``/``**kwargs`` are
    the abstract or concrete operands to lower for.  The convenience the
    traffic analyzer uses: one
    call from a callable to the traffic model's {flops, hbm_bytes,
    collective_*} dict.  ``detail=True`` adds the per-computation
    breakdown and the ``fusion_ops`` histogram (see :func:`parse_module`)
    so a bytes regression points at the computation that grew.
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    return parse_module(compiled.as_text(), detail=detail)


# ---- legacy summary API (kept for tests/benchmarks) ------------------------


def collective_stats(hlo_text: str) -> dict:
    r = parse_module(hlo_text)
    return {"total": {"count": r["collective_count"],
                      "wire_bytes": r["collective_wire_bytes"]},
            "by_op": r["collective_by_op"]}


def fusion_stats(hlo_text: str) -> dict:
    """Op histogram of the optimized module (entry only, unscaled) — used
    in §Perf to spot redundant gathers / transposes."""
    ops = defaultdict(int)
    for m in re.finditer(r"=\s*(?:[\w\[\],<>{}\s]*?)\s([a-z][\w\-]*)\(",
                         hlo_text):
        ops[m.group(1)] += 1
    keep = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute", "transpose", "reshape", "copy", "fusion",
            "while", "dot", "convolution", "dynamic-slice",
            "dynamic-update-slice", "gather", "scatter")
    return {k: ops[k] for k in keep if ops[k]}
