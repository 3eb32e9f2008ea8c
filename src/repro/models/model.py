"""Unified decoder model over heterogeneous block stacks.

A model is a sequence of *segments* ``(pattern, repeat)`` where pattern is a
tuple of block types:

  attn   — pre-norm GQA attention (full/SWA/local) + MLP
  moe    — attention + mixture-of-experts FFN (merge-based dispatch)
  ssd    — Mamba2 state-space block
  rglru  — RG-LRU recurrent block + MLP (RecurrentGemma)

Each segment is applied with ``lax.scan`` over its ``repeat`` axis (compact
HLO for 80-layer models) with optional remat.  Three entry points mirror
the dry-run shapes: ``loss_and_aux`` (train), ``prefill``, ``decode_step``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.obs import scope
from . import layers as L
from . import moe as M
from . import rglru as R
from . import ssm as S
from .losses import chunked_cross_entropy


# ------------------------------------------------------------- blocks ------


def init_block(key, btype: str, cfg) -> dict:
    ks = jax.random.split(key, 4)
    nk, nb = cfg.norm, cfg.norm_bias
    d = cfg.d_model
    if btype in ("attn", "moe"):
        p = {"ln1": L.init_norm(d, nk, jnp.float32, nb),
             "attn": L.init_attention(ks[0], cfg)}
        if btype == "attn":
            p["ln2"] = L.init_norm(d, nk, jnp.float32, nb)
            p["mlp"] = L.init_mlp(ks[1], cfg)
        else:
            p["ln2"] = L.init_norm(d, nk, jnp.float32, nb)
            p["moe"] = M.init_moe(ks[1], cfg)
        return p
    if btype == "ssd":
        return {"ln1": L.init_norm(d, nk, jnp.float32, nb),
                "ssd": S.init_ssd(ks[0], cfg)}
    if btype == "rglru":
        return {"ln1": L.init_norm(d, nk, jnp.float32, nb),
                "rec": R.init_rglru(ks[0], cfg),
                "ln2": L.init_norm(d, nk, jnp.float32, nb),
                "mlp": L.init_mlp(ks[1], cfg)}
    raise ValueError(btype)


def init_block_cache(btype: str, cfg, batch: int, cache_len: int):
    if btype in ("attn", "moe"):
        kvh, dh = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, cache_len, kvh, dh), cfg.cdtype),
                "v": jnp.zeros((batch, cache_len, kvh, dh), cfg.cdtype)}
    if btype == "ssd":
        return S.init_ssd_state(cfg, batch)
    if btype == "rglru":
        return R.init_rglru_state(cfg, batch)
    raise ValueError(btype)


def _window(cfg) -> int | None:
    return None if cfg.attention == "full" else cfg.window


def block_apply(p, btype, x, cfg, *, positions=None, cache=None, pos=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    # Norm outputs are constrained so the *cotangent* resharding in the
    # backward (dx = dh @ W^T is partial over the model axis) is pinned
    # here, where primal & cotangent are bf16 — otherwise the partitioner
    # reshards the f32 carrier downstream at 2× wire (§Perf iteration 3).
    rs = cfg.residual_spec
    # Layer scopes (repro.obs.SCOPES) label every device op for the
    # profiler join; each sublayer's residual add rides in its scope.
    if btype in ("attn", "moe"):
        with scope("norm"):
            h = constrain(L.norm_apply(p["ln1"], x, cfg.norm, cfg.norm_eps),
                          *rs)
        with scope("attn"):
            attn_out, new_cache = L.attention_apply(
                p["attn"], h, cfg, window=_window(cfg), positions=positions,
                cache=cache, pos=pos)
        if getattr(cfg, "parallel_block", False):
            with scope("mlp"):
                if btype == "moe":
                    ffn_out, aux = M.moe_apply(p["moe"], h, cfg)
                else:
                    ffn_out = L.mlp_apply(p["mlp"], h, cfg)
                x = x + attn_out + ffn_out
        else:
            with scope("attn"):
                x = x + attn_out
            with scope("norm"):
                h2 = constrain(
                    L.norm_apply(p["ln2"], x, cfg.norm, cfg.norm_eps), *rs)
            with scope("mlp"):
                if btype == "moe":
                    ffn_out, aux = M.moe_apply(p["moe"], h2, cfg)
                else:
                    ffn_out = L.mlp_apply(p["mlp"], h2, cfg)
                x = x + ffn_out
        return x, new_cache, aux
    # Recurrent blocks: a multi-token input is a prefill — the state is
    # computed from scratch (the passed cache is only a shape donor).
    if btype == "ssd":
        with scope("norm"):
            h = L.norm_apply(p["ln1"], x, cfg.norm, cfg.norm_eps)
        state = cache if (cache is not None and x.shape[1] == 1) else None
        out, new_cache = S.ssd_apply(p["ssd"], h, cfg, state=state)
        return x + out, new_cache, aux
    if btype == "rglru":
        with scope("norm"):
            h = L.norm_apply(p["ln1"], x, cfg.norm, cfg.norm_eps)
        state = cache if (cache is not None and x.shape[1] == 1) else None
        out, new_cache = R.rglru_apply(p["rec"], h, cfg, state=state)
        x = x + out
        with scope("norm"):
            h2 = L.norm_apply(p["ln2"], x, cfg.norm, cfg.norm_eps)
        with scope("mlp"):
            x = x + L.mlp_apply(p["mlp"], h2, cfg)
        return x, new_cache, aux
    raise ValueError(btype)


# ------------------------------------------------------------- params ------


def init_params(cfg, key) -> dict:
    keys = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab_size
    params: dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        params["embed"] = (jax.random.normal(keys[0], (v, d), cfg.pdtype)
                           * d ** -0.5)
        if not cfg.tie_embeddings:
            params["unembed"] = (jax.random.normal(keys[1], (v, d),
                                                   cfg.pdtype) * d ** -0.5)
    else:  # stub modality frontend provides embeddings; head is untied
        params["unembed"] = (jax.random.normal(keys[1], (v, d), cfg.pdtype)
                             * d ** -0.5)
    params["final_norm"] = L.init_norm(d, cfg.norm, jnp.float32,
                                       cfg.norm_bias)

    segments = []
    kseg = keys[2]
    for pattern, count in cfg.segments:
        seg = []
        for pos_i, btype in enumerate(pattern):
            kseg, sub = jax.random.split(kseg)
            bkeys = jax.random.split(sub, count)
            seg.append(jax.vmap(
                lambda k, bt=btype: init_block(k, bt, cfg))(bkeys))
        segments.append(seg)
    params["segments"] = segments
    return params


def init_caches(cfg, batch: int, cache_len: int) -> list:
    caches = []
    for pattern, count in cfg.segments:
        seg = []
        for btype in pattern:
            one = init_block_cache(btype, cfg, batch, cache_len)
            seg.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (count,) + x.shape), one))
        caches.append(seg)
    return caches


# ------------------------------------------------------------ forward ------


def forward(params, cfg, h, *, positions=None, caches=None, pos=None,
            remat: bool = False):
    """h (b, s, d) embedded inputs → (h, new_caches, aux_total)."""
    h = constrain(h, *cfg.residual_spec)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for si, (pattern, count) in enumerate(cfg.segments):
        seg_params = tuple(params["segments"][si])
        seg_caches = tuple(caches[si]) if caches is not None else None

        def step(carry, xs, pattern=pattern):
            hh, aux = carry
            if seg_caches is None:
                lp = xs
                lc = (None,) * len(pattern)
            else:
                lp, lc = xs
            outs = []
            for pi, btype in enumerate(pattern):
                hh, nc, a = block_apply(lp[pi], btype, hh, cfg,
                                        positions=positions, cache=lc[pi],
                                        pos=pos)
                hh = constrain(hh, *cfg.residual_spec)  # residual layout
                outs.append(nc)
                aux = aux + a
            return (hh, aux), tuple(outs)

        fn = jax.checkpoint(step) if remat else step
        xs = seg_params if seg_caches is None else (seg_params, seg_caches)
        (h, aux_total), seg_new = jax.lax.scan(fn, (h, aux_total), xs)
        new_caches.append(list(seg_new))
    return h, new_caches, aux_total


# ------------------------------------------------------------ embed/head ---


def embed_inputs(params, cfg, batch: dict, *, positions=None):
    with scope("embed"):
        if cfg.input_mode == "tokens":
            h = jnp.take(params["embed"], batch["tokens"], axis=0)
            h = h.astype(cfg.cdtype)
            if getattr(cfg, "embed_scale", False):
                h = h * jnp.asarray(cfg.d_model ** 0.5, cfg.cdtype)
        else:
            h = batch["embeds"].astype(cfg.cdtype)
        if cfg.rope_theta == 0.0:  # sinusoidal absolute positions (musicgen)
            if positions is None:
                positions = jnp.arange(h.shape[1])[None, :]
            h = h + L.sinusoidal(positions, cfg.d_model).astype(h.dtype)
        return h


def unembed_matrix(params, cfg):
    return params.get("unembed", params.get("embed"))


def final_logits(logits, cfg):
    """The head's published scaling of raw logits: ``logit_scale``, then
    the soft cap."""
    if cfg.logit_scale != 1.0:
        logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


# ------------------------------------------------------- entry points ------


def loss_and_aux(params, cfg, batch, *, remat: bool = True,
                 loss_chunk: int = 512, aux_weight: float = 0.01):
    """Causal-LM loss.  batch: tokens/embeds (b,s[,d]), labels (b,s)."""
    h = embed_inputs(params, cfg, batch)
    h, _, aux = forward(params, cfg, h, remat=remat)
    h = L.norm_apply(params["final_norm"], h, cfg.norm, cfg.norm_eps)
    if cfg.logit_scale != 1.0:   # (h s) E^T = s (h E^T)
        h = h * jnp.asarray(cfg.logit_scale, h.dtype)
    nll, cnt = chunked_cross_entropy(
        h, unembed_matrix(params, cfg), batch["labels"],
        chunk=loss_chunk, logit_softcap=cfg.logit_softcap,
        mask=batch.get("mask"))
    return nll + aux_weight * aux, {"nll": nll, "aux": aux, "tokens": cnt}


def prefill(params, cfg, batch, *, cache_len: int | None = None):
    """Forward pass that fills caches.  Returns (caches, last_logits, pos)."""
    h = embed_inputs(params, cfg, batch)
    b, s, _ = h.shape
    cache_len = cache_len or s
    caches = init_caches(cfg, b, cache_len)
    h, caches, _ = forward(params, cfg, h, caches=caches)
    h_last = L.norm_apply(params["final_norm"], h[:, -1:], cfg.norm,
                          cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", h_last,
                        unembed_matrix(params, cfg).astype(h_last.dtype),
                        preferred_element_type=jnp.float32)
    return caches, final_logits(logits, cfg), jnp.full((b,), s, jnp.int32)


def decode_step(params, cfg, caches, batch, pos):
    """One-token step.  batch: tokens (b,1) or embeds (b,1,d); pos (b,).

    Returns (logits (b,1,v), new_caches)."""
    positions = pos[:, None]
    h = embed_inputs(params, cfg, batch, positions=positions)
    h, caches, _ = forward(params, cfg, h, positions=positions,
                           caches=caches, pos=pos)
    h = L.norm_apply(params["final_norm"], h, cfg.norm, cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", h,
                        unembed_matrix(params, cfg).astype(h.dtype),
                        preferred_element_type=jnp.float32)
    return final_logits(logits, cfg), caches
