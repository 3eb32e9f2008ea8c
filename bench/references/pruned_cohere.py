"""Plain reference of Cohere's decoder (c4ai-command-r-v01, model_type
``cohere``) whose three MLP matrices are held as CSR, in ``jax.numpy``
and float32.

It imports nothing of the program.  It reads the weights the benchmark
made from the seed (dense attention and embedding, each MLP matrix as CSR
arrays) and runs the forward pass one layer at a time at
``Precision.HIGHEST``, as transformers' ``CohereDecoderLayer`` and
``CohereForCausalLM`` write it:

    h = E[tokens]
    per layer:  a = LN(h) * w_ln     (one weight-only LayerNorm: mean
                                      subtracted, no bias, eps)
                q, k, v = a Wq, a Wk, a Wv
                RoPE on interleaved pairs (x[..., 0::2], x[..., 1::2])
                attn = softmax_causal(q k^T / sqrt(dh)) v Wo
                mlp = (silu(a W1^T) * (a W3^T)) W2^T
                h = h + attn + mlp          (the parallel block)
    logits = ((LN(h) * w_f) E^T) * logit_scale     (tied embedding)

So that it fits beside the weights, each MLP matrix is made dense on its
own, one at a time, and the logits are computed where the embedding's
rows lie (on a chip holding a share of the vocabulary, that share).
``quant="fp8"`` is the control of ``bench/references/pruned_lm.py``:
every matrix product takes both operands rounded to float8 (e4m3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.references.pruned_lm import _mm, dense_of


def layernorm(x, w, eps):
    """Weight-only LayerNorm, statistics in float32."""
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w


def rope_interleaved(x, theta):
    """x (b, s, h, dh): pair ``(x[2i], x[2i+1])`` turned by ``p * theta **
    (-2i / dh)`` at position ``p`` in 0..s-1."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq   # (s, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def attention(h, ln, at, dims, quant):
    """The normed input ``a`` and the attention branch of one layer;
    ``dims`` = (heads, kv_heads, theta, eps)."""
    heads, kvh, theta, eps = dims
    b, s, d = h.shape
    dh = d // heads
    g = heads // kvh
    a = layernorm(h, ln, eps)
    q = _mm("bsd,de->bse", a, at["wq"], quant).reshape(b, s, heads, dh)
    k = _mm("bsd,de->bse", a, at["wk"], quant).reshape(b, s, kvh, dh)
    v = _mm("bsd,de->bse", a, at["wv"], quant).reshape(b, s, kvh, dh)
    q, k = rope_interleaved(q, theta), rope_interleaved(k, theta)
    q = q.reshape(b, s, kvh, g, dh)
    sc = _mm("bqkgd,bskd->bkgqs", q, k, quant) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _mm("bkgqs,bskd->bqkgd", p, v, quant).reshape(b, s, heads * dh)
    return a, _mm("bse,ed->bsd", o, at["wo"], quant)


@functools.partial(jax.jit, static_argnames=("shape", "spec", "quant"))
def sparse_mm(x, csr, shape, spec, quant):
    """``x`` against one MLP matrix ``(d_out, d_in)``, made dense here."""
    w = dense_of(csr["row_ptr"], csr["col_ind"], csr["vals"], shape)
    return _mm(spec, x, w, quant)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "quant"))
def head(h, final, embed, eps, scale, quant):
    return _mm("bsd,vd->bsv", layernorm(h, final, eps), embed, quant) * scale


def forward(weights, tokens, cfg: dict, quant: str | None = None):
    """Logits (b, s, vocab) float32 of ``tokens`` (b, s)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    eps = float(cfg["layer_norm_eps"])
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            float(cfg["rope_theta"]), eps)
    h = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0)
    for lw in weights["layers"]:
        a, attn = attention(h, lw["ln1"], lw["attn"], dims, quant)
        mlp = lw["mlp"]
        up = jax.nn.silu(sparse_mm(a, mlp["w1"], (ff, d), "bsd,fd->bsf",
                                   quant)) \
            * sparse_mm(a, mlp["w3"], (ff, d), "bsd,fd->bsf", quant)
        h = h + attn + sparse_mm(up, mlp["w2"], (d, ff), "bsf,df->bsd",
                                 quant)
    return head(h, weights["final_norm"], weights["embed"], eps,
                float(cfg["logit_scale"]), quant)
