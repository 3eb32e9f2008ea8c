"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward/train step on CPU, asserting output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import model as M
from repro.optim import adamw
from repro.runtime import steps as R


def _batch(cfg, b=2, s=16, key=0):
    k = jax.random.PRNGKey(key)
    if cfg.input_mode == "tokens":
        toks = jax.random.randint(k, (b, s + 1), 0, cfg.vocab_size)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return {"embeds": jax.random.normal(k, (b, s, cfg.d_model), jnp.float32),
            "labels": jax.random.randint(k, (b, s), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The full configs carry the exact published numbers."""
    cfg = get_config(arch)
    expected = {
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "command-r-35b": (40, 8192, 64, 64, 22528, 256000),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected
    if arch == "olmoe-1b-7b":
        assert (cfg.num_experts, cfg.top_k) == (64, 8)
    if arch == "mixtral-8x22b":
        assert (cfg.num_experts, cfg.top_k) == (8, 2)
        assert cfg.attention == "swa"
    if arch == "qwen2-72b":
        assert cfg.qkv_bias
    if arch == "mamba2-1.3b":
        assert cfg.ssm_state == 128
    if arch == "recurrentgemma-2b":
        assert cfg.attention == "local" and cfg.window == 2048


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    h = M.embed_inputs(params, cfg, batch)
    h, _, aux = M.forward(params, cfg, h)
    assert h.shape == (2, 16, cfg.d_model)
    assert np.isfinite(np.asarray(h, np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    step = R.make_train_step(cfg, adamw.AdamWConfig(warmup_steps=2,
                                                    total_steps=10),
                             loss_chunk=8)
    state = R.init_train_state(cfg, jax.random.PRNGKey(0))
    state2, metrics = jax.jit(step)(state, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["skipped"]) == 0.0
    # params actually moved
    moved = jax.tree.reduce(
        lambda a, kv: a + float(jnp.sum(jnp.abs(kv))), jax.tree.map(
            lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)),
            state["params"], state2["params"]), 0.0)
    assert moved > 0.0


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-1.3b",
                                  "recurrentgemma-2b", "olmoe-1b-7b",
                                  "musicgen-large"])
def test_smoke_decode_shapes(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 8
    batch = _batch(cfg, b, s)
    batch.pop("labels")
    caches, logits, pos = M.prefill(params, cfg, batch, cache_len=s + 4)
    assert logits.shape == (b, 1, cfg.vocab_size)
    step_in = ({"tokens": jnp.zeros((b, 1), jnp.int32)}
               if cfg.input_mode == "tokens" else
               {"embeds": jnp.zeros((b, 1, cfg.d_model), jnp.float32)})
    lg, caches2 = M.decode_step(params, cfg, caches, step_in, pos)
    assert lg.shape == (b, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg, np.float32)).all()
    # cache pytree structure is stable across steps (scan compatibility)
    jax.tree.map(lambda a, b: None, caches, caches2)


def test_microbatched_train_step_matches_single():
    """Gradient accumulation is loss-equivalent to one big batch."""
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"),
                              compute_dtype="float32")
    ocfg = adamw.AdamWConfig(warmup_steps=2, total_steps=10)
    b = _batch(cfg, b=4, s=16)
    state = R.init_train_state(cfg, jax.random.PRNGKey(0))
    s1 = R.make_train_step(cfg, ocfg, microbatches=1, loss_chunk=8)
    s2 = R.make_train_step(cfg, ocfg, microbatches=2, loss_chunk=8)
    mb = jax.tree.map(lambda x: x.reshape(2, 2, *x.shape[1:]), b)
    st1, m1 = jax.jit(s1)(state, b)
    st2, m2 = jax.jit(s2)(state, mb)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     st1["params"], st2["params"])
    assert max(jax.tree.leaves(d)) < 5e-5
