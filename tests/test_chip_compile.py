"""The SpMM kernels of the serving path compile for a TPU v5e.

Interpret mode runs the kernel bodies in Python on the CPU and never asks
Mosaic (the TPU kernel compiler) whether a block shape, a gather or an
on-chip footprint is legal.  These tests do: each compiles one forward
kernel for a described (not attached) v5e chip at a llama3.2-1b MLP shape
with 25% of the weights kept, and checks that the compiled program holds
the kernel as a ``tpu_custom_call`` named after the kernel's ``name=``
(what the benchmark's trace reduction finds it by).  Nothing runs, so
this says nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers a
module that touched it on import would change what each worker collects.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.csr import CSR
from repro.kernels import merge_spmm, ops, rowgroup_spmm, rowsplit_spmm

KEEP = 0.25
TOKENS = 128          # one lane tile of activations: B is (1, d_in, 128)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mlp_shapes():
    """(label, m, k) of the pruned MLP matrices, stored as (d_out, d_in)."""
    cfg = get_config("llama3.2-1b")
    return [("w1", cfg.d_ff, cfg.d_model), ("w2", cfg.d_model, cfg.d_ff)]


def _csr_shapes(m, k):
    """Shape-only CSR with ``KEEP`` of every row kept."""
    per_row = int(k * KEEP)
    nnz = m * per_row
    return CSR(jax.ShapeDtypeStruct((m + 1,), jnp.int32),
               jax.ShapeDtypeStruct((nnz,), jnp.int32),
               jax.ShapeDtypeStruct((nnz,), jnp.float32), (m, k)), per_row


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, structure, a, k):
    """The instruction names of the compiled program's kernels."""
    b = jax.ShapeDtypeStruct((1, k, TOKENS), jnp.float32)
    compiled = jax.jit(fn).lower(*_on(sharding, (structure, a.vals, b))) \
        .compile()
    names = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*"
                       r'custom_call_target="tpu_custom_call"',
                       compiled.as_text(), re.M)
    assert names
    return names


@pytest.mark.parametrize("label,m,k", _mlp_shapes())
def test_merge_compiles_for_v5e(one_chip, label, m, k):
    a, _ = _csr_shapes(m, k)
    t = merge_spmm.default_t(m, a.nnz_pad)
    structure = jax.eval_shape(
        lambda a: merge_spmm.plan_merge_structure(a, t=t), a)
    names = _compile(lambda s, v, b: ops.merge_execute(s, v, b, m=m,
                                                        interpret=False),
                     one_chip, structure, a, k)
    assert all("merge_spmm" in n for n in names), names


@pytest.mark.parametrize("label,m,k", _mlp_shapes())
def test_rowsplit_compiles_for_v5e(one_chip, label, m, k):
    a, per_row = _csr_shapes(m, k)
    structure = jax.eval_shape(
        lambda a: rowsplit_spmm.plan_rowsplit_structure(a, l_pad=per_row),
        a)
    names = _compile(lambda s, v, b: ops.rowsplit_execute(s, v, b, m=m,
                                                           interpret=False),
                     one_chip, structure, a, k)
    assert all("rowsplit_spmm" in n for n in names), names


def test_rowgroup_compiles_for_v5e(one_chip):
    # Equal row lengths (magnitude pruning keeps KEEP of every row) make
    # one length bucket; two buckets compile as two such launches.
    _, m, k = _mlp_shapes()[0]
    a, per_row = _csr_shapes(m, k)
    groups = ((m, per_row),)
    fwd = dict(groups=(jax.eval_shape(
        lambda a: rowsplit_spmm.ell_slots(a, jnp.arange(m), per_row), a),),
        inv_pos=jax.ShapeDtypeStruct((m,), jnp.int32))
    names = _compile(lambda s, v, b: rowgroup_spmm.rowgroup_execute_parts(
        groups, rowsplit_spmm.DEFAULT_TL, s, v, b, interpret=False),
        one_chip, fwd, a, k)
    # Rowgroup launches the row-split kernel, once per length bucket.
    assert all("rowsplit_spmm" in n for n in names), names
