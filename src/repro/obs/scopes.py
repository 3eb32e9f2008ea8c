"""The program's scope vocabulary, and the join from device ops to it.

Every device operation of a jitted program is one instruction of its
optimized HLO, and the instruction carries the name stack it was traced
under (``metadata={op_name="jit(fwd)/mlp/spmm/gather_vals/gather"}``).
The program opens ``jax.named_scope`` from one fixed vocabulary
(:data:`SCOPES`, through :func:`scope`) around each layer of the model
step and each part of an SpMM call, always: a named scope is trace-time
metadata and costs nothing when the program runs.

:func:`device_scopes` reads a compiled program and maps each instruction
name (``fusion.12``, ``copy.2``, ``rowsplit_spmm.1``) to the innermost
vocabulary scope it belongs to.  A profiler capture names each device op
by its instruction text alone, with no metadata (the ``XLA Ops`` line of
a ``/device:`` plane), so the join is by instruction name: an operator
reads any ``jax.profiler`` capture of a program by layer as

    scopes = obs.device_scopes(jax.jit(f).lower(*args).compile())
    layer = scopes.get(event_name.split(" = ")[0].lstrip("%"))

Stdlib only; the HLO parser (``repro.analysis.hlo``) and JAX load on
first use.
"""
from __future__ import annotations

import re

#: Every scope the program opens, with what it holds.  The model step's
#: scopes first, then the parts of one SpMM call (nested inside ``spmm``).
SCOPES = {
    "embed": "token embedding lookup",
    "attn": "attention: projections, rotary embedding, softmax, output "
            "projection and its residual add",
    "norm": "RMS or layer norm",
    "mlp": "the feed-forward block (dense, MoE or SpMM matmuls), its "
           "activation and its residual add",
    "unembed": "the logits projection",
    "spmm": "one sparse-times-dense product: the parts below",
    "gather_vals": "per-call values gathered into the plan's slot order "
                   "(apply_vals, smem_vals) and the live-slot counts the "
                   "kernel prefetches",
    "widen_b": "the dense operand laid out as B (a sparse layer's "
               "transpose) and widened to float32 for the kernel's "
               "one-row slices",
    "pad": "padding to tile multiples: B's lanes and K panels, slot "
           "windows, epilogue operands",
    "kernel": "the Pallas kernel launch, or its XLA twin under "
              "impl='xla'",
    "unpad": "the output sliced and reshaped to the caller's shape (a "
             "sparse layer's transpose back), and any post-hoc epilogue "
             "or row un-permutation",
    "shard_vals": "a device-sharded product's per-call gather of each "
                  "shard's values out of the global value vector",
    "shard_concat": "a device-sharded product's output assembled from "
                    "its shards: row blocks brought together (and the "
                    "collective that does it), or column partials summed",
}

#: The ``name=`` of every ``pallas_call`` in ``repro.kernels``, and the
#: scope its launch belongs to.  The TPU compiler names a Mosaic kernel's
#: custom call after this name and drops its metadata, so
#: :func:`device_scopes` reads the scope from the name for those.
KERNELS = {
    "rowsplit_spmm": "kernel",
    "merge_spmm": "kernel",
    "sddmm": "kernel",
    "moe_gemm": "mlp",
    "flash_attention": "attn",
}


def scope(name: str):
    """``jax.named_scope(name)`` for a vocabulary scope; any other name
    raises, so the vocabulary stays in this one place."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a scope of repro.obs.SCOPES")
    import jax
    return jax.named_scope(name)


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"\b(?:calls|body)=%([\w.\-]+)")
# Computations that run inside another instruction, never as device ops.
_INNER_RE = re.compile(r"\b(?:calls|to_apply)=%([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_WRAPPER_RE = re.compile(r"^(?:\w+\()+|\)+$")
# Instructions that never run as a device op of their own.
_NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element",
            "bitcast", "after-all", "partition-id", "replica-id"}


def scope_of(op_name: str) -> str | None:
    """Innermost vocabulary scope of an ``op_name``: its last path part is
    the primitive (``.../spmm/pad/pad`` is the pad primitive in the
    ``pad`` scope), and transforms wrap the parts before it
    (``transpose(jvp(mlp))`` reads as ``mlp``)."""
    for part in reversed(op_name.split("/")[:-1]):
        part = _WRAPPER_RE.sub("", part)
        if part in SCOPES:
            return part
    return None


def _kernel_scope(name: str) -> str | None:
    base, dot, suffix = name.rpartition(".")
    return KERNELS.get(base if dot and suffix.isdigit() else name)


def _traced(op_name: str) -> bool:
    """Whether an ``op_name`` names an op the program traced
    (``jit(f)/mlp/...``) rather than a parameter (``"b"``) or nothing."""
    return "/" in op_name


_UNDECIDED = object()


def device_scopes(compiled) -> dict[str, str | None]:
    """Instruction name -> innermost vocabulary scope, for every
    instruction of ``compiled`` (a ``jax.stages.Compiled`` or its optimized
    HLO text) that does work on the device; ``None`` for an op the program
    traced outside every scope.

    An instruction the program traced takes the scope of its own
    ``op_name`` or, for a fusion, of its fused computation's root (the
    first traced op found, root first); that decides it, scope or
    ``None``: no other instruction lends it one.  Only an instruction
    with no traced ``op_name`` at all takes a scope from elsewhere: a
    Mosaic kernel's custom call from its name (:data:`KERNELS`); an
    async copy or slice, or a relayout of a parameter, from the
    instructions that use its result, and last from its operands.
    """
    from repro.analysis.hlo import _INSTR_RE, _split_computations

    text = compiled if isinstance(compiled, str) else compiled.as_text()
    comps = _split_computations(text)
    inner = set(_INNER_RE.findall(text))
    parsed: dict[str, list] = {}
    for cname, lines in comps.items():
        rows = []
        for line in lines:
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            name, _, opcode, rest = m.groups()
            op_name = _OP_NAME_RE.search(rest)
            calls = _CALLS_RE.findall(rest)
            rows.append((name, opcode, rest,
                         op_name.group(1) if op_name else "", calls))
        parsed[cname] = rows

    memo: dict = {}

    def traced(op_name: str, calls: list):
        """Scope of the instruction's own traced ``op_name``, else of its
        fused computation's first traced op, root first; None where what
        is traced lies outside every scope, _UNDECIDED where nothing is."""
        own = scope_of(op_name) if _traced(op_name) else _UNDECIDED
        if isinstance(own, str):
            return own
        for c in calls:
            if c not in memo:
                memo[c] = _UNDECIDED
                for _, _, _, o, cc in reversed(parsed.get(c, [])):
                    r = traced(o, cc)
                    if r is not _UNDECIDED:
                        memo[c] = r
                        break
            if memo[c] is not _UNDECIDED:
                return memo[c]
        return own

    out: dict[str, str | None] = {}
    for cname, rows in parsed.items():
        if cname in inner:
            continue
        names = {r[0] for r in rows}
        found: dict[str, str | None] = {}
        open_: set = set()
        operands: dict[str, list] = {}
        users: dict[str, list] = {n: [] for n in names}
        for name, opcode, rest, op_name, calls in rows:
            operands[name] = [o for o in _OPERAND_RE.findall(rest)
                              if o in names and o != name]
            for o in operands[name]:
                users[o].append(name)
            r = traced(op_name, calls)
            if r is _UNDECIDED:
                r = _kernel_scope(name) if opcode == "custom-call" else None
                if r is None:
                    open_.add(name)
            found[name] = r
        for name, *_ in reversed(rows):
            if name in open_:
                found[name] = next((found[u] for u in users[name]
                                    if found[u]), None)
        for name, *_ in rows:
            if name in open_ and found[name] is None:
                found[name] = next((found[o] for o in operands[name]
                                    if found[o]), None)
        for name, opcode, *_ in rows:
            if opcode not in _NO_WORK:
                out[name] = found[name]
    return out
