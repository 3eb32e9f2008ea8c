"""The program's scope vocabulary reaches the compiled program, and
``repro.obs.device_scopes`` maps every device op back to it.

Compiles on the CPU with the Pallas kernels in interpret mode: a tiny
pruned forward per SpMM method and a tiny merge aggregation, with
``REPRO_TRACE`` off (the scopes are always on).  The join itself is
checked on hand-written optimized-HLO text shaped like the TPU
compiler's (tiled layouts, async copies, a Mosaic kernel's custom call).
"""
import ast
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.core import (PlanPolicy, SparseMatrix, build_plan, execute_plan,
                        random_csr)
from repro.launch import serve
from repro.models import model as M
from repro.obs.scopes import _OP_NAME_RE, scope_of

SPMM_PARTS = {"gather_vals", "widen_b", "pad", "kernel", "unpad"}


def _op_name_scopes(compiled) -> set:
    """Every vocabulary scope named in the compiled program's metadata,
    fused computations included."""
    return {scope_of(m) for m in _OP_NAME_RE.findall(compiled.as_text())}


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("granite-3-2b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("method", ["rowsplit", "merge"])
def test_pruned_forward_every_op_scoped(smoke, method):
    assert not obs.is_enabled()        # tier-1 runs untraced
    cfg, params = smoke
    blocks = serve.prune_ffn_blocks(
        params, cfg, 0.25,
        policy=PlanPolicy(method=method, with_transpose=False))
    compiled = jax.jit(serve.make_pruned_forward(cfg)).lower(
        params, blocks, jnp.zeros((1, 8), jnp.int32)).compile()
    scopes = obs.device_scopes(compiled)
    assert scopes and all(v in obs.SCOPES for v in scopes.values()), \
        sorted(k for k, v in scopes.items() if v is None)
    named = _op_name_scopes(compiled)
    assert {"embed", "attn", "norm", "mlp", "unembed",
            "spmm"} | SPMM_PARTS <= named
    assert {"embed", "attn", "norm", "unembed", "kernel",
            "gather_vals"} <= set(scopes.values())


@pytest.mark.parametrize("method", ["rowsplit", "merge"])
def test_spmm_parts_reach_the_program(method):
    # A ragged float32 B (lane padding, a sliced output) beside an aligned
    # bfloat16 one (widened in the wrapper), one program.
    a = random_csr(jax.random.PRNGKey(3), 20, 24, nnz_per_row=(0, 6))
    plan = build_plan(a, method=method, with_transpose=False)

    def f(v, b16, b32):
        return execute_plan(plan, v, b16), execute_plan(plan, v, b32)

    lowered = jax.jit(f).lower(a.vals, jnp.ones((24, 128), jnp.bfloat16),
                               jnp.ones((24, 40), jnp.float32))
    traced = lowered.as_text(debug_info=True)      # name-stack locations
    for part in SPMM_PARTS | {"spmm"}:
        assert re.search(rf'["/]{part}/', traced), part
    # The CPU compiler folds this widening away; what survives is scoped.
    compiled = lowered.compile()
    assert SPMM_PARTS - {"widen_b"} <= _op_name_scopes(compiled)
    scopes = obs.device_scopes(compiled)
    assert all(v in obs.SCOPES for v in scopes.values())
    assert {"kernel", "gather_vals", "pad", "unpad"} <= set(scopes.values())


def test_merge_aggregation_every_op_scoped():
    a = SparseMatrix.from_csr(
        random_csr(jax.random.PRNGKey(5), 64, 64, nnz_per_row=(0, 12)),
        PlanPolicy(method="merge", with_transpose=False))
    compiled = jax.jit(lambda a, x: a @ x).lower(
        a, jnp.ones((64, 40), jnp.float32)).compile()
    scopes = obs.device_scopes(compiled)
    assert scopes and all(v in obs.SCOPES for v in scopes.values())
    assert {"gather_vals", "kernel", "unpad"} <= set(scopes.values())


def test_an_op_traced_outside_every_scope_reads_none():
    """The map lends no scope to an op the program traced outside the
    vocabulary, so the checks above fail when a new op is left out."""
    a = SparseMatrix.from_csr(
        random_csr(jax.random.PRNGKey(5), 64, 64, nnz_per_row=(0, 12)),
        PlanPolicy(method="merge", with_transpose=False))
    compiled = jax.jit(lambda a, x: jnp.tanh(a @ x)).lower(
        a, jnp.ones((64, 40), jnp.float32)).compile()
    scopes = obs.device_scopes(compiled)
    assert None in scopes.values()
    assert {"gather_vals", "kernel"} <= set(scopes.values())


# ------------------------------------------------------------- the join ---

# Shaped like the TPU compiler's output for one SpMM (abridged): async
# prefetches without metadata, a relayout carrying a parameter's name, a
# fusion whose root was traced outside every scope, and the Mosaic kernel.
TPU_HLO = """\
HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[4097], param_1: s32[4096]) -> f32[4096] {
  %param_0 = f32[4097]{0:T(1024)S(1)} parameter(0)
  %param_1 = s32[4096]{0:T(1024)S(1)} parameter(1)
  ROOT %gather.1 = f32[4096]{0:T(1024)} gather(%param_0, %param_1), \
metadata={op_name="jit(f)/mlp/spmm/gather_vals/gather"}
}

%fused_computation.1 (param_0.1: f32[4096]) -> f32[4096] {
  %param_0.1 = f32[4096]{0:T(1024)} parameter(0)
  ROOT %convert.3 = f32[4096]{0:T(1024)} convert(%param_0.1), \
metadata={op_name="jit(f)/convert_element_type"}
}

ENTRY %main.3 (v.1: f32[4097], s.1: s32[4096], b.1: bf16[512,128]) \
-> f32[256,128] {
  %v.1 = f32[4097]{0:T(1024)} parameter(0), metadata={op_name="v"}
  %s.1 = s32[4096]{0:T(1024)} parameter(1), metadata={op_name="s"}
  %b.1 = bf16[512,128]{1,0:T(8,128)(2,1)} parameter(2), \
metadata={op_name="b"}
  %copy-start = (f32[4097]{0:T(1024)S(1)}, f32[4097]{0:T(1024)}, \
u32[]{:S(2)}) copy-start(%v.1)
  %copy-done = f32[4097]{0:T(1024)S(1)} copy-done(%copy-start)
  %fusion = f32[4096]{0:T(1024)} fusion(%copy-done, %s.1), kind=kCustom, \
calls=%fused_computation, metadata={op_name="jit(f)/convert"}
  %fusion.1 = f32[4096]{0:T(1024)} fusion(%fusion), kind=kLoop, \
calls=%fused_computation.1
  %copy.2 = bf16[512,128]{1,0:T(8,128)(2,1)} copy(%b.1), \
metadata={op_name="b"}
  %convert.1 = f32[512,128]{1,0:T(8,128)} convert(%copy.2), \
metadata={op_name="jit(f)/mlp/spmm/widen_b/convert_element_type"}
  %rowsplit_spmm.1 = f32[256,128]{1,0:T(8,128)} custom-call(%fusion.1, \
%convert.1), custom_call_target="tpu_custom_call", metadata={}
  ROOT %slice.4 = f32[256,128]{1,0:T(8,128)} slice(%rowsplit_spmm.1), \
slice={[0:256], [0:128]}, \
metadata={op_name="jit(f)/mlp/spmm/unpad/slice"}
}
"""


def test_device_scopes_join_rules():
    scopes = obs.device_scopes(TPU_HLO)
    assert scopes == {
        # prefetch of the values: from the op it feeds
        "copy-start": "gather_vals", "copy-done": "gather_vals",
        # own op_name has no scope: from the fused computation's root
        "fusion": "gather_vals",
        # traced outside every scope (its fused root's op_name): None,
        # whatever its user or operand
        "fusion.1": None,
        # a relayout named after its parameter: from its user
        "copy.2": "widen_b", "convert.1": "widen_b",
        # the Mosaic kernel, no metadata: from its name= (obs.KERNELS)
        "rowsplit_spmm.1": "kernel",
        "slice.4": "unpad",
    }


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/mlp/spmm/pad/pad", "pad"),          # the pad primitive
    ("jit(f)/attn/pad", "attn"),                 # not the pad scope
    ("jit(f)/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(f)/mlp/spmm/spmm/jit(rowsplit_execute)/gather_vals/gather",
     "gather_vals"),
    ("b", None),
    ("", None),
])
def test_scope_of_reads_the_innermost_scope(op_name, want):
    assert scope_of(op_name) == want


def test_scope_rejects_names_outside_the_vocabulary():
    with obs.scope("kernel"):
        pass
    with pytest.raises(ValueError, match="not a scope"):
        obs.scope("spmm_merge_pallas")
    assert set(obs.KERNELS.values()) <= set(obs.SCOPES)


def test_every_pallas_call_is_named():
    """Each ``pallas_call`` in ``repro.kernels`` passes a ``name=`` from
    ``obs.KERNELS``: the trace finds a kernel by that name."""
    import repro.kernels
    root = os.path.dirname(repro.kernels.__file__)
    named = {}
    for f in sorted(os.listdir(root)):
        if not f.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(root, f)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "attr", None) == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{f}:{node.lineno} has no name="
                named[kw["name"].value] = f
    assert set(named) == set(obs.KERNELS)
