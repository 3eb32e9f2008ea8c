"""Grouped (expert) GEMM with merge-based load balancing — Pallas TPU.

The paper's nonzero-split principle applied to MoE: the token→expert routing
matrix is sparse, hot experts are "long rows" (Type 1 imbalance), cold
experts "short rows" (Type 2).  Sorting tokens by expert puts the problem in
CSR order; padding each expert's token count to the token-tile ``TT`` plays
the role of the paper's chunk breaks at CTA boundaries (the group-boundary
analogue of the carry-out fix-up); the grid then assigns an *equal number of
tokens per step*, with the expert's weight block fetched through a
scalar-prefetched dynamic ``index_map`` — load balance is perfect by
construction regardless of the routing distribution.
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

TT = 64    # tokens per grid step (the merge chunk)
TDN = 128  # output-feature lanes
TDK = 512  # reduction tile


def plan_groups(group_sizes: jax.Array, tokens_pad: int, tt: int = TT):
    """Map each token-block of ``tt`` sorted tokens to its expert.

    ``group_sizes`` (E,) are *padded* group sizes, each a multiple of ``tt``
    and summing to ``tokens_pad`` (callers pad with dummy tokens; see
    models/moe.py).  Returns ``block_expert`` (tokens_pad//tt,) int32.
    """
    n_blocks = tokens_pad // tt
    ends = jnp.cumsum(group_sizes)
    starts = jnp.arange(n_blocks, dtype=group_sizes.dtype) * tt
    return jnp.searchsorted(ends, starts, side="right").astype(jnp.int32)


def _moe_kernel(be_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def launch_models(block_expert, *, tokens: int, d_in: int, d_out: int,
                  n_experts: int, tt: int = TT, tdn: int = TDN,
                  tdk: int = TDK, dtype: str = "float32"):
    """Static model of :func:`moe_group_gemm_pallas` (introspect.py) —
    mirrors the BlockSpecs below for the access/traffic analyses.
    ``block_expert`` is the concrete (host) block→expert stream."""
    import numpy as np

    from .introspect import KernelBlock, KernelLaunch
    be = np.asarray(block_expert)
    n_k = d_in // tdk
    n_b = tokens // tt
    blocks = [
        KernelBlock("block_expert", (n_b,), "int32", None, (n_b,),
                    "scalar"),
        KernelBlock("x", (tt, tdk), dtype,
                    lambda bi, j, kk: (bi, kk), (tokens, d_in), "in"),
        KernelBlock("w", (1, tdk, tdn), dtype,
                    lambda bi, j, kk: (be[bi], kk, j),
                    (n_experts, d_in, d_out), "in"),
    ]
    out = KernelBlock("o", (tt, tdn), dtype,
                      lambda bi, j, kk: (bi, j), (tokens, d_out), "out")
    blocks += [out, KernelBlock("acc", (tt, tdn), "float32", None,
                                (tt, tdn), "scratch")]
    return [KernelLaunch(
        label="moe_gemm", grid=(n_b, d_out // tdn, n_k),
        blocks=tuple(blocks),
        flush=lambda bi, j, kk: kk == n_k - 1, out=out)]


def moe_group_gemm_pallas(x: jax.Array, w: jax.Array,
                          block_expert: jax.Array, *, tt: int = TT,
                          tdn: int = TDN, tdk: int = TDK,
                          interpret: bool = False) -> jax.Array:
    """y[i] = x[i] @ w[expert_of_block(i // tt)].

    x (tokens_pad, d_in), w (E, d_in, d_out); tokens_pad % tt == 0,
    d_in % tdk == 0, d_out % tdn == 0 (ops.py pads).
    """
    tokens, d_in = x.shape
    _, _, d_out = w.shape
    n_k = d_in // tdk
    grid = (tokens // tt, d_out // tdn, n_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tt, tdk), lambda bi, j, kk, be: (bi, kk)),
            pl.BlockSpec((1, tdk, tdn), lambda bi, j, kk, be: (be[bi], kk, j)),
        ],
        out_specs=pl.BlockSpec((tt, tdn), lambda bi, j, kk, be: (bi, j)),
        scratch_shapes=[pltpu.VMEM((tt, tdn), jnp.float32)],
    )
    kernel = functools.partial(_moe_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, d_out), x.dtype),
        interpret=interpret,
        name="moe_gemm",
    )(block_expert, x, w)
