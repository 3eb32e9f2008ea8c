"""Causal flash attention — Pallas TPU kernel (beyond-paper hot spot).

The §Roofline tables show every training cell pays a large memory term in
the attention inner loops (online-softmax carries + score blocks).  This
kernel applies the same design principles the paper uses for SpMM:

* the 128-lane dimension is the coalescing unit (head_dim on the lanes),
* the grid streams KV blocks through VMEM while the (q-block × head) C
  tile stays resident — one flush per output tile, like the merge kernel's
  revisit-accumulation,
* the causal band is *skipped structurally*: the KV grid dimension is
  clamped per q-block (no masked-out compute), the banded analogue of
  row-split's "only touch the nonzeroes you own".

Layout: q (b, h, sq, dh), k/v (b, h, skv, dh) — heads pre-broadcast for
GQA by the wrapper (ops-level; the model path keeps using the XLA flash
implementation, this kernel is the TPU serving/training drop-in).
"""
from __future__ import annotations

import functools

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, scale: float, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # only blocks with kpos_min <= qpos_max survive the grid clamp; the
    # diagonal block still needs the elementwise causal mask
    q = q_ref[0]                                   # (bq, dh)
    k = k_ref[0]                                   # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def launch_models(*, bh: int, s: int, dh: int, bq: int = DEFAULT_BQ,
                  bk: int = DEFAULT_BK, dtype: str = "float32"):
    """Static model of :func:`flash_attention_pallas` (introspect.py) —
    mirrors the BlockSpecs below for the access/traffic analyses."""
    from .introspect import KernelBlock, KernelLaunch
    n_q = s // bq
    n_k = s // bk
    blocks = [
        KernelBlock("q", (1, bq, dh), dtype,
                    lambda b, z, i, j: (b, i, 0), (bh, s, dh), "in"),
        KernelBlock("k", (1, bk, dh), dtype,
                    lambda b, z, i, j: (b, j, 0), (bh, s, dh), "in"),
        KernelBlock("v", (1, bk, dh), dtype,
                    lambda b, z, i, j: (b, j, 0), (bh, s, dh), "in"),
    ]
    out = KernelBlock("o", (1, bq, dh), dtype,
                      lambda b, z, i, j: (b, i, 0), (bh, s, dh), "out")
    blocks += [
        out,
        KernelBlock("m", (bq, 1), "float32", None, (bq, 1), "scratch"),
        KernelBlock("l", (bq, 1), "float32", None, (bq, 1), "scratch"),
        KernelBlock("acc", (bq, dh), "float32", None, (bq, dh),
                    "scratch"),
    ]
    return [KernelLaunch(
        label="flash_attention", grid=(bh, 1, n_q, n_k),
        blocks=tuple(blocks),
        flush=lambda b, z, i, j: j == n_k - 1, out=out)]


def flash_attention_pallas(q, k, v, *, bq: int = DEFAULT_BQ,
                           bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jax.Array:
    """q/k/v (bh, s, dh) with identical head counts (GQA pre-broadcast).

    Causal; s % bq == 0 == s % bk (ops.py pads).  The kv grid dim is NOT
    clamped per-q (Pallas grids are rectangular) but out-of-band blocks
    exit via the mask producing zero updates; structural skipping is done
    by the wrapper slicing the band for long sequences.
    """
    bh, s, dh = q.shape
    scale = dh ** -0.5
    n_q = s // bq
    n_k = s // bk
    grid = (bh, 1, n_q, n_k)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, _, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, _, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, _, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, _, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, dh), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
