"""Plain reference of a pre-norm GQA decoder with a SwiGLU MLP whose three
matrices are held as CSR, in ``jax.numpy`` and float32.

It imports nothing of the program.  It reads the weights the benchmark
made from the seed (dense attention and embedding, and each MLP matrix
as CSR arrays), rebuilds each MLP matrix densely with its pruned entries
as zeros, and runs the forward pass one layer at a time at
``Precision.HIGHEST``:

    h = E[tokens]
    per layer:  a = rmsnorm(h) * ln1
                q, k, v = a Wq, a Wk, a Wv;  RoPE (rotate-half) on q, k
                h += softmax_causal(q k^T / sqrt(dh)) v Wo   (GQA groups)
                b = rmsnorm(h) * ln2
                h += (silu(b W1^T) * (b W3^T)) W2^T
    logits = (rmsnorm(h) * final) E^T        (tied embedding)

``quant="fp8"`` is the control: every matrix product takes both operands
rounded to float8 (e4m3: 4 exponent and 3 mantissa bits, one scale per
tensor), the precision below the configuration's bfloat16.  The rounding
is ``lax.reduce_precision``, which the compiler keeps (a float8 round
trip through ``astype`` may be elided as excess precision).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 240.0           # largest finite value at 4 exponent bits, 3 mantissa


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _mm(spec, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def dense_of(row_ptr, col_ind, vals, shape):
    """The (m, k) matrix a CSR stores, zeros elsewhere."""
    m, k = shape
    nnz = col_ind.shape[0]
    rows = jnp.repeat(jnp.arange(m, dtype=jnp.int32),
                      jnp.diff(row_ptr), total_repeat_length=nnz)
    return jnp.zeros((m, k), jnp.float32).at[rows, col_ind].add(
        vals.astype(jnp.float32))


def rmsnorm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def rope(x, theta):
    """x (b, s, h, dh): rotate-half RoPE at positions 0..s-1."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq   # (s, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def layer(h, lw, dims, quant):
    """One decoder layer; ``dims`` = (heads, kv_heads, ff, theta, eps)."""
    heads, kvh, ff, theta, eps = dims
    b, s, d = h.shape
    dh = d // heads
    g = heads // kvh
    at = lw["attn"]
    a = rmsnorm(h, lw["ln1"], eps)
    q = _mm("bsd,de->bse", a, at["wq"], quant).reshape(b, s, heads, dh)
    k = _mm("bsd,de->bse", a, at["wk"], quant).reshape(b, s, kvh, dh)
    v = _mm("bsd,de->bse", a, at["wv"], quant).reshape(b, s, kvh, dh)
    q, k = rope(q, theta), rope(k, theta)
    q = q.reshape(b, s, kvh, g, dh)
    sc = _mm("bqkgd,bskd->bkgqs", q, k, quant) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("bkgqs,bskd->bqkgd", p, v, quant).reshape(b, s, heads * dh)
    h = h + _mm("bse,ed->bsd", o, at["wo"], quant)
    c = rmsnorm(h, lw["ln2"], eps)
    mats = {}
    for name, (m, kk) in (("w1", (ff, d)), ("w3", (ff, d)), ("w2", (d, ff))):
        csr = lw["mlp"][name]
        mats[name] = dense_of(csr["row_ptr"], csr["col_ind"], csr["vals"],
                              (m, kk))
    u = jax.nn.silu(_mm("bsd,fd->bsf", c, mats["w1"], quant)) \
        * _mm("bsd,fd->bsf", c, mats["w3"], quant)
    return h + _mm("bsf,df->bsd", u, mats["w2"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(h, final, embed, eps, quant):
    return _mm("bsd,vd->bsv", rmsnorm(h, final, eps), embed, quant)


def forward(weights, tokens, cfg: dict, quant: str | None = None):
    """Logits (b, s, vocab) float32 of ``tokens`` (b, s)."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]))
    h = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0)
    for lw in weights["layers"]:
        h = layer(h, lw, dims, quant)
    return head(h, weights["final_norm"], weights["embed"],
                float(cfg["rms_norm_eps"]), quant)
