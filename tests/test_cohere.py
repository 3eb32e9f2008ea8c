"""Cohere's decoder block (command-r-35b) through the program's pruned
forward, against the plain float32 reference the benchmark decides
``correct`` with (``bench/references/pruned_cohere.py``), at the smoke
size on the CPU: on one device, and row-sharded over four with the
embedding by vocabulary rows and attention by heads (the placement of
``serve --mesh``).

The block: one weight-only LayerNorm (eps 1e-5) feeds attention and the
MLP in parallel, rotary embedding on interleaved pairs, logits scaled by
``logit_scale``.  The program computes in float32 here, as the
reference does, so the two differ by summation order alone.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch import serve
from repro.models import layers as L
from repro.models import model as M

KEEP = 0.25
NDEV = 4
IN_CHILD = os.environ.get("_REPRO_FORCED_CHILD") == "1"
# float32 on both sides over widths of 64-128: only the order of the sums
# differs, a few float32 ulps of the logits.
TOL = 1e-4


def _cfg(**kw):
    return dataclasses.replace(get_smoke_config("command-r-35b"),
                               compute_dtype="float32", **kw)


def _model(cfg, seed=0):
    """Seeded weights with norm scales away from 1, and a prompt."""
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, key)
    k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 1), 3)
    for p, k in ((params["segments"][0][0]["ln1"], k1),
                 (params["final_norm"], k2)):
        p["scale"] = p["scale"] + 0.1 * jax.random.normal(k,
                                                          p["scale"].shape)
    tokens = jax.random.randint(k3, (1, 16), 0, cfg.vocab_size)
    return params, tokens


def _head(params):
    return {"embed": params["embed"], "final_norm": params["final_norm"]}


def _reference(cfg, params, blocks, tokens):
    from bench.references import pruned_cohere as ref
    layers = [{"ln1": lp["ln1"]["scale"], "attn": lp["attn"],
               "mlp": {name: {"row_ptr": sl.weight.row_ptr,
                              "col_ind": sl.weight.col_ind,
                              "vals": sl.weight.vals}
                       for name, sl in lp["mlp"].items()}}
              for lp in blocks]
    weights = {"embed": params["embed"],
               "final_norm": params["final_norm"]["scale"],
               "layers": layers}
    dims = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "rope_theta": cfg.rope_theta, "layer_norm_eps": cfg.norm_eps,
            "logit_scale": cfg.logit_scale}
    return np.asarray(ref.forward(weights, np.asarray(tokens), dims))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2)))


def test_config_is_the_published_block():
    from repro.configs import get_config
    c = get_config("command-r-35b")
    assert (c.d_model, c.num_heads, c.num_kv_heads, c.head_dim, c.d_ff,
            c.vocab_size) == (8192, 64, 64, 128, 22528, 256000)
    assert (c.norm, c.norm_eps, c.norm_bias, c.parallel_block,
            c.rope_interleaved, c.logit_scale, c.rope_theta) == \
        ("layernorm", 1e-5, False, True, True, 0.0625, 8e6)
    s = get_smoke_config("command-r-35b")
    assert (s.norm_eps, s.norm_bias, s.rope_interleaved, s.logit_scale,
            s.parallel_block) == (1e-5, False, True, 0.0625, True)
    assert "bias" not in M.init_params(s, jax.random.PRNGKey(0))[
        "final_norm"]


@pytest.mark.parametrize("interleaved", [True, False])
def test_rope_turns_its_pairs(interleaved):
    """Pair ``j`` at position ``p`` turns by ``p * theta ** (-j / half)``:
    the pairs are ``(2j, 2j+1)`` interleaved, ``(j, j + half)`` else."""
    s, h, dh, theta = 7, 3, 16, 8e6
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (2, s, h, dh)))
    pos = np.arange(s)
    got = np.asarray(L.rope(jnp.asarray(x), jnp.asarray(pos)[None], theta,
                            interleaved))
    want = np.empty_like(x, dtype=np.float64)
    half = dh // 2
    for j in range(half):
        a, b = (2 * j, 2 * j + 1) if interleaved else (j, j + half)
        ang = pos * theta ** (-j / half)
        c, sn = np.cos(ang)[:, None], np.sin(ang)[:, None]
        want[..., a] = x[..., a] * c - x[..., b] * sn
        want[..., b] = x[..., b] * c + x[..., a] * sn
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_weight_only_layernorm_honours_eps():
    """At a variance near eps, eps moves the output: 1e-5 is applied."""
    x = 3e-3 * jax.random.normal(jax.random.PRNGKey(4), (4, 64))
    p = L.init_norm(64, "layernorm", jnp.float32, bias=False)
    assert set(p) == {"scale"}
    xf = np.asarray(x, np.float64)
    mu = xf.mean(-1, keepdims=True)
    want = (xf - mu) / np.sqrt(((xf - mu) ** 2).mean(-1, keepdims=True)
                               + 1e-5)
    np.testing.assert_allclose(L.norm_apply(p, x, "layernorm", 1e-5), want,
                               rtol=1e-4)
    assert _rel(L.norm_apply(p, x, "layernorm"), want) > 1e-2


def test_pruned_forward_matches_reference():
    cfg = _cfg()
    params, tokens = _model(cfg)
    blocks = serve.prune_ffn_blocks(params, cfg, KEEP)
    fwd = jax.jit(serve.make_pruned_forward(cfg))
    want = _reference(cfg, params, blocks, tokens)
    assert _rel(fwd(_head(params), blocks, tokens), want) < TOL
    # Each published part moves the logits far beyond the tolerance: the
    # comparison would catch a program that left one out.
    for part in (dict(rope_interleaved=False), dict(logit_scale=1.0),
                 dict(norm_eps=1.0), dict(parallel_block=False)):
        off = dataclasses.replace(cfg, **part)
        got = jax.jit(serve.make_pruned_forward(off))(_head(params), blocks,
                                                      tokens)
        assert _rel(got, want) > 100 * TOL, part


def _check_placement(head, blocks, mesh):
    """Each device holds its own shard of the embedding's rows, of the
    attention heads and of every sharded plan, and nothing of another
    device's; every plan runs as one program over the mesh."""
    devices = list(mesh.devices.flat)
    n = len(devices)

    def owns(x, axis):
        size = x.shape[axis] // n
        for s in x.addressable_shards:
            i = devices.index(s.device)
            idx = s.index[axis]
            assert (idx.start or 0, idx.stop) == (i * size, (i + 1) * size)
            assert s.data.shape[axis] == size

    owns(head["embed"], 0)
    for lp in blocks:
        for w in ("wq", "wk", "wv"):
            owns(lp["attn"][w], 1)
        owns(lp["attn"]["wo"], 0)
        assert lp["ln1"]["scale"].sharding.is_fully_replicated
        for sl in lp["mlp"].values():
            plan = sl.plan
            assert plan.meta.spmd_mesh() is mesh
            assert sl.weight.vals.sharding.is_fully_replicated
            stacked, slots, _ = plan._stacked()
            for i, shard in enumerate(plan.shards):
                for leaf in jax.tree.leaves(shard):
                    assert leaf.devices() == {devices[i]}
            for leaf, per in zip(jax.tree.leaves((stacked, slots)),
                                 zip(*[jax.tree.leaves((p, s))
                                       for p, s in zip(plan.shards,
                                                       plan.vals_slots)])):
                for s in leaf.addressable_shards:
                    i = devices.index(s.device)
                    np.testing.assert_array_equal(np.asarray(s.data),
                                                  np.asarray(per[i]))


def test_sharded_forward_matches_unsharded_and_reference(forced_device_run):
    """Four devices: as ``serve --mesh 4`` places the model (in a child
    process given four when this one has fewer)."""
    if jax.device_count() < NDEV and not IN_CHILD:
        r = forced_device_run(
            f"{__file__}::test_sharded_forward_matches_unsharded_and_"
            "reference", NDEV)
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
        return
    from jax.sharding import Mesh

    from repro.core import PlanPolicy, ShardSpec
    cfg = _cfg()
    params, tokens = _model(cfg, seed=1)
    fwd = jax.jit(serve.make_pruned_forward(cfg))
    blocks = serve.prune_ffn_blocks(params, cfg, KEEP)
    whole = np.asarray(fwd(_head(params), blocks, tokens))
    want = _reference(cfg, params, blocks, tokens)

    mesh = Mesh(np.array(jax.devices()[:NDEV]), ("data",))
    policy = PlanPolicy(with_transpose=False, shards=ShardSpec(mesh=mesh))
    sharded = serve.prune_ffn_blocks(params, cfg, KEEP, policy=policy)
    head, sharded = serve.place_on_mesh(_head(params), sharded, mesh)
    _check_placement(head, sharded, mesh)
    assert "shard_map" in str(jax.make_jaxpr(
        serve.make_pruned_forward(cfg))(head, sharded, tokens))
    got = fwd(head, sharded, tokens)
    # The same float32 products over the same rows: only the order of the
    # attention output's sum over heads (now an all-reduce) differs.
    assert _rel(got, whole) < 1e-5
    assert _rel(got, want) < TOL
