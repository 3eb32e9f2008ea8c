"""Transformer building blocks: norms, RoPE, blockwise (flash) attention
with GQA / sliding-window / local variants, SwiGLU & GELU MLPs.

Pure-functional: ``init_*`` builds param pytrees (plain dicts), ``*_apply``
consumes them.  All sequence-mixing ops are written blockwise (``lax.scan``
over query/key chunks with online softmax) so activation memory stays
bounded at 32k prefill and the HLO stays compact for the dry-run.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

# ---------------------------------------------------------------- norms ----


def init_norm(d: int, kind: str, dtype, bias: bool = True) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm" and bias:
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p: dict, x: jax.Array, kind: str = "rmsnorm",
               eps: float = 1e-6) -> jax.Array:
    """Statistics in f32, elementwise math in the input dtype.

    Keeping the *tensor* in bf16 matters for distribution, not just speed:
    upcasting x before the normalize lets the SPMD partitioner hoist the
    convert between the reduce-scatter/all-gather halves of the TP
    all-reduce, doubling collective bytes (§Perf iteration 3).  The f32
    reduction below fuses into the reduce — no f32 copy of x exists.
    """
    if kind == "rmsnorm":
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        y = x * jax.lax.rsqrt(ms + eps).astype(x.dtype)
    else:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (x - mu.astype(x.dtype)) * jax.lax.rsqrt(var + eps).astype(x.dtype)
    y = y * p["scale"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


# ----------------------------------------------------------------- RoPE ----


def rope(x: jax.Array, positions: jax.Array, theta: float,
         interleaved: bool = False) -> jax.Array:
    """x (..., s, h, dh), positions (..., s) broadcastable.  Pair ``i``
    turns by ``positions * theta ** (-i / (dh / 2))``; the pairs are
    ``(x[i], x[i + dh/2])`` (rotate-half) or, ``interleaved``,
    ``(x[2i], x[2i+1])``."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq   # (..., s, half)
    ang = ang[..., None, :]                            # (..., s, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = x[..., :half], x[..., half:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freq = 10_000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------- blockwise attention -------


def _attend_block(q, k, v, qpos, kpos, carry, *, scale, window, softcap):
    """Online-softmax update for one (q-chunk, kv-chunk) pair.

    q (b, cq, kv, g, dh); k/v (b, ck, kv, dh); positions (cq,), (ck,).
    carry = (m, l, acc) with shapes (b, kv, g, cq[, dh]).
    """
    m, l, acc = carry
    # bf16 MACs with f32 accumulation — the MXU-native regime, and
    # numerically consistent with decode_attention.
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(jnp.isfinite(m_new)[..., None], p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
    l = l * alpha + p.sum(-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def flash_attention(q, k, v, *, q_offset=0, window: int | None = None,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    softcap: float = 0.0) -> jax.Array:
    """Causal blockwise attention.  q (b,sq,h,dh), k/v (b,skv,kv,dh).

    ``q_offset``: absolute position of q[0] (prefill continuation).
    ``window``: sliding-window size (SWA/local); None = full attention.
    Windowed variants only *fetch* the KV chunks a query chunk can see
    (dynamic_slice of size window+q_chunk) — sub-quadratic compute, the
    banded analogue of the paper's "only touch the nonzeroes you own".
    """
    b, sq, h, dh = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    scale = dh ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    n_q = -(-sq // q_chunk)
    assert sq % q_chunk == 0, (sq, q_chunk)
    qg = q.reshape(b, sq, kvh, g, dh)

    if window is not None:
        span = kv_chunk * (-(-(window + q_chunk) // kv_chunk))
        span = min(span, skv)
    else:
        span = skv

    def q_step(_, qi):
        q_blk = jax.lax.dynamic_slice_in_dim(qg, qi * q_chunk, q_chunk, 1)
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
        if window is not None:
            start = jnp.clip(q_offset + (qi + 1) * q_chunk - span, 0,
                             skv - span)
        else:
            start = 0
        k_win = jax.lax.dynamic_slice_in_dim(k, start, span, 1)
        v_win = jax.lax.dynamic_slice_in_dim(v, start, span, 1)
        m0 = jnp.full((b, kvh, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, dh), jnp.float32)

        # checkpoint: backward re-materializes one (q,kv)-chunk of scores
        # at a time instead of saving every p matrix (O(s²) otherwise).
        @jax.checkpoint
        def kv_step(carry, ki):
            k_blk = jax.lax.dynamic_slice_in_dim(k_win, ki * kv_chunk,
                                                 kv_chunk, 1)
            v_blk = jax.lax.dynamic_slice_in_dim(v_win, ki * kv_chunk,
                                                 kv_chunk, 1)
            kpos = start + ki * kv_chunk + jnp.arange(kv_chunk)
            return _attend_block(q_blk, k_blk, v_blk, qpos, kpos, carry,
                                 scale=scale, window=window,
                                 softcap=softcap), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(span // kv_chunk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (b, kv, g, cq, dh) -> (b, cq, kv*g, dh)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, dh)
        return None, out.astype(q.dtype)

    _, chunks = jax.lax.scan(jax.checkpoint(q_step), None, jnp.arange(n_q))
    # chunks (n_q, b, q_chunk, h, dh)
    return chunks.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)


def decode_attention(q, k_cache, v_cache, pos, *,
                     window: int | None = None,
                     softcap: float = 0.0) -> jax.Array:
    """One-token attention against a cache.  q (b,1,h,dh); caches
    (b,S,kv,dh); pos (b,) current position (number of tokens already in
    cache).  Windowed archs slice only the window from the cache."""
    b, _, h, dh = q.shape
    _, s_cache, kvh, _ = k_cache.shape
    g = h // kvh
    scale = dh ** -0.5
    full_span = window is None or window >= s_cache
    if full_span:
        # attend over the whole cache in place — no slicing, no gather
        span = s_cache
        k_win, v_win = k_cache, v_cache
        kpos = jnp.broadcast_to(jnp.arange(span)[None], (b, span))
        # flash-decoding: keep the cache sequence-sharded; softmax
        # reductions over seq become partial-reduce + tiny all-reduce
        # instead of an all-gather of the (huge) cache.
        k_win = constrain(k_win, "dp", "model", None, None)
        v_win = constrain(v_win, "dp", "model", None, None)
    else:
        span = window
        start = jnp.clip(pos + 1 - span, 0, s_cache - span)
        k_win = jax.vmap(
            lambda kc, st: jax.lax.dynamic_slice_in_dim(kc, st, span, 0))(
                k_cache, start)
        v_win = jax.vmap(
            lambda vc, st: jax.lax.dynamic_slice_in_dim(vc, st, span, 0))(
                v_cache, start)
        kpos = start[:, None] + jnp.arange(span)[None]       # (b, span)
    qg = q.reshape(b, kvh, g, dh)
    # bf16 inputs, f32 accumulation — never materialize an f32 cache copy
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_win,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = kpos <= pos[:, None]                              # causal
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    if full_span:
        s = constrain(s, "dp", None, None, "model")
    # numerically-safe softmax over the (possibly sharded) seq axis
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jax.lax.stop_gradient(m))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_win.dtype), v_win,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dh).astype(q.dtype)


# ------------------------------------------------------------ attention ----


def init_attention(key, cfg) -> dict:
    d, h, kvh, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h * dh), cfg.pdtype) * s,
        "wk": jax.random.normal(ks[1], (d, kvh * dh), cfg.pdtype) * s,
        "wv": jax.random.normal(ks[2], (d, kvh * dh), cfg.pdtype) * s,
        "wo": jax.random.normal(ks[3], (h * dh, d), cfg.pdtype) * s,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), cfg.pdtype)
        p["bk"] = jnp.zeros((kvh * dh,), cfg.pdtype)
        p["bv"] = jnp.zeros((kvh * dh,), cfg.pdtype)
    return p


def _qkv(p, x, cfg):
    b, s, _ = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.cdtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    # pin head-sharded layouts (TP over heads; batch over dp); in pure-FSDP
    # mode heads stay local and the batch spans every device
    bt = "dp" if cfg.tp else "dpm"
    ht = "model" if cfg.tp else None
    q = constrain(q.reshape(b, s, h, dh), bt, None, ht, None)
    k = constrain(k.reshape(b, s, kvh, dh), bt, None, ht, None)
    v = constrain(v.reshape(b, s, kvh, dh), bt, None, ht, None)
    return q, k, v


def attention_apply(p, x, cfg, *, window=None, positions=None,
                    cache=None, pos=None):
    """x (b, s, d).  Training/prefill when cache is None or being filled;
    decode when s == 1 and cache holds prior KV.

    Returns (out, new_cache) where cache = {"k","v"} (b, S, kv, dh)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta, cfg.rope_interleaved)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_interleaved)
    softcap = cfg.attn_logit_softcap
    # larger flash tiles cut online-softmax carry traffic ~10% (§Perf
    # iteration 7); capped at 512 for long sequences to bound the f32
    # score block (b·kv·g·cq·ck) on 16 GiB chips
    chunk = 1024 if s <= 8192 else 512
    if cache is None:
        out = flash_attention(q, k, v, window=window, softcap=softcap,
                              q_chunk=chunk, kv_chunk=chunk)
        new_cache = {"k": k, "v": v}
    elif s == 1:
        kc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, 0))(cache["k"], k, pos)
        vc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, 0))(cache["v"], v, pos)
        out = decode_attention(q, kc, vc, pos, window=window, softcap=softcap)
        new_cache = {"k": kc, "v": vc}
    else:  # prefill into an allocated cache
        out = flash_attention(q, k, v, window=window, softcap=softcap,
                              q_chunk=chunk, kv_chunk=chunk)
        s_cache = cache["k"].shape[1]
        pad = [(0, 0), (0, s_cache - s), (0, 0), (0, 0)]
        new_cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    out = out.reshape(b, s, -1) @ p["wo"].astype(cfg.cdtype)
    return constrain(out, *cfg.residual_spec), new_cache


# ----------------------------------------------------------------- MLPs ----


def init_mlp(key, cfg, d_ff=None) -> dict:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    s = d ** -0.5
    if getattr(cfg, "mlp", "swiglu") == "gelu":
        return {"w1": jax.random.normal(ks[0], (d, ff), cfg.pdtype) * s,
                "w2": (jax.random.normal(ks[1], (ff, d), cfg.pdtype)
                       * ff ** -0.5)}
    return {"w1": jax.random.normal(ks[0], (d, ff), cfg.pdtype) * s,
            "w3": jax.random.normal(ks[1], (d, ff), cfg.pdtype) * s,
            "w2": jax.random.normal(ks[2], (ff, d), cfg.pdtype) * ff ** -0.5}


def mlp_apply(p, x, cfg):
    if callable(p.get("w1")):
        # SparseLinear (pruned-FFN serving/fine-tuning): the layer carries
        # its own SpmmPlan and kernel choice — see repro/models/sparse.py.
        from repro.models.sparse import sparse_mlp_apply
        return sparse_mlp_apply(p, x, cfg)
    dt = cfg.cdtype
    if "w3" in p:
        h = jax.nn.silu(x @ p["w1"].astype(dt)) * (x @ p["w3"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["w1"].astype(dt))
    if cfg.tp:
        h = constrain(h, "dp", None, "model")   # ff dim TP-sharded
    else:
        h = constrain(h, "dpm", None, None)
    return constrain(h @ p["w2"].astype(dt), *cfg.residual_spec)
