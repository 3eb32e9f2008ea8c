"""Plain reference of one sparse aggregation ``C = A @ X``, in
``jax.numpy`` and float32.

It imports nothing of the program: it reads the CSR arrays and features
the benchmark made from the seed and sums ``A[r, c] * X[c, :]`` into row
``r`` edge by edge (``segment_sum``), in blocks of edges so that it fits.
Alongside it returns ``N = |A| @ |X|``, the scale each output's rounding
error is measured against.

``quant="high"`` is the control: each product is formed the way a
three-pass bfloat16 matrix unit forms float32 ones (``a_hi x_hi + a_hi
x_lo + a_lo x_hi``), the precision below the float32 the configuration
states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EDGE_BLOCK = 1 << 18


def _bf16(x):
    # ``reduce_precision`` rounds as a bfloat16 cast would, and the
    # compiler keeps it (a cast round trip may be elided as excess
    # precision).
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


@functools.partial(jax.jit, static_argnames=("m", "quant"),
                   donate_argnums=(0, 1))
def _block(c, n, rows, cols, vals, x, m, quant):
    xg = x[cols]                                   # (E, f)
    v = vals[:, None]
    if quant == "high":
        vh, vl = _split(v)
        xh, xl = _split(xg)
        p = vh * xh + vh * xl + vl * xh
    else:
        p = v * xg
    c = c + jax.ops.segment_sum(p, rows, num_segments=m)
    n = n + jax.ops.segment_sum(jnp.abs(v) * jnp.abs(xg), rows,
                                num_segments=m)
    return c, n


def aggregate(row_ptr, col_ind, vals, x, quant: str | None = None):
    """``(C, N)``: the aggregation and its error scale, both (m, f)."""
    m = row_ptr.shape[0] - 1
    nnz = col_ind.shape[0]
    rows = jnp.repeat(jnp.arange(m, dtype=jnp.int32), jnp.diff(row_ptr),
                      total_repeat_length=nnz)
    pad = (-nnz) % EDGE_BLOCK
    rows = jnp.pad(rows, (0, pad))
    cols = jnp.pad(col_ind, (0, pad))
    vals = jnp.pad(vals.astype(jnp.float32), (0, pad))   # zero weight
    c = jnp.zeros((m, x.shape[1]), jnp.float32)
    n = jnp.zeros_like(c)
    for s in range(0, nnz + pad, EDGE_BLOCK):
        e = slice(s, s + EDGE_BLOCK)
        c, n = _block(c, n, rows[e], cols[e], vals[e], x, m, quant)
    return c, n
