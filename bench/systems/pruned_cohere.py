"""Cohere's decoder (parallel block) with pruned MLP matrices, spread
over the cell's chips, run through the program.

Its weights are placed as the program's serving path places them on its
mesh (``repro.launch.serve.dense_shardings`` and ``place_on_mesh``, the
code of ``serve --mesh``): the embedding by vocabulary rows, the
attention matrices by heads, the norms whole on each chip, and each MLP
matrix as its global CSR whole on each chip, beside a plan row-sharded
by nonzeros over the chips (``SparseLinear.shard``), one shard a chip.
Set-up makes every weight on the chips from the seed, a matrix at a time
(one ``(m, k)`` sort for a pruned pattern at once, never a layer's or the
model's), each drawn as ``bench/systems/pruned_lm.py`` draws it; each
chip makes only what it holds.  It then builds the plans, compiles
the program's pruned forward (``make_pruned_forward``) as one SPMD
program over the chips at the shape the cell's traffic sends, and warms
it.

The check compares the logits the timed path produced with the plain
reference (``bench/references/pruned_cohere.py``) run on the same
weights, where they lie, with the numbers of ``pruned_lm.compare``.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import traffic as T
from bench import work
from bench.references import pruned_cohere as ref
from bench.systems import pruned_lm as lm

AXIS = "data"


def _dense(key, cfg_items):
    """One layer's norm and dense attention."""
    cfg = dict(cfg_items)
    dm = lm._dims(cfg)
    d, hd = dm["d"], dm["hd"]
    k = jax.random.split(key, 8)
    s = d ** -0.5
    attn = {"wq": jax.random.normal(k[0], (d, dm["heads"] * hd)) * s,
            "wk": jax.random.normal(k[1], (d, dm["kv"] * hd)) * s,
            "wv": jax.random.normal(k[2], (d, dm["kv"] * hd)) * s,
            "wo": jax.random.normal(k[3], (dm["heads"] * hd, d)) * s}
    return {"ln1": 1.0 + 0.1 * jax.random.normal(k[7], (d,)), "attn": attn}


def _mlp(key, cfg_items, j):
    """One layer's MLP matrix ``j`` (w1, w3, w2) as CSR."""
    cfg = dict(cfg_items)
    _, m, kk, sc = lm._mlp_shapes(cfg)[j]
    return lm._pruned_csr(jax.random.split(key, 8)[4 + j], m, kk,
                          cfg[lm.KEEP_KEY], sc)


def _head(key, cfg_items):
    cfg = dict(cfg_items)
    d = cfg["hidden_size"]
    ke, kn = jax.random.split(key)
    return {"embed": jax.random.normal(ke, (cfg["vocab_size"], d)) * d ** -0.5,
            "final_norm": 1.0 + 0.1 * jax.random.normal(kn, (d,))}


class System:
    """One pruned Cohere decoder over its cell's chips, under one traffic
    mix."""

    CONTROL = "fp8"

    def __init__(self, cfg: dict, traffic: dict, seed: int, clock,
                 devices):
        self.cfg, self.traffic, self.seed, self.clock = cfg, traffic, seed, \
            clock
        self.devices = devices
        # First, before any work: a program without the block's published
        # parts fails here.
        self.mcfg = self.model_config()
        self.mesh = Mesh(np.array(devices), (AXIS,))
        self.dims = lm._dims(cfg)
        self.nnz_per_layer = sum(
            m * max(1, int(round(cfg[lm.KEEP_KEY] * kk)))
            for _, m, kk, _ in lm._mlp_shapes(cfg))

    # ------------------------------------------------------------ set-up ---

    def model_config(self):
        from repro.configs.base import ModelConfig
        c = self.cfg
        return ModelConfig(
            name=c["name"], family="dense",
            num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            norm="layernorm", norm_eps=float(c["layer_norm_eps"]),
            norm_bias=False, parallel_block=True,
            tie_embeddings=c["tie_word_embeddings"],
            rope_theta=float(c["rope_theta"]), rope_interleaved=True,
            logit_scale=float(c["logit_scale"]),
            param_dtype=c["torch_dtype"], compute_dtype=c["compute_dtype"])

    def _generator(self, fn, key, **kw):
        """``fn`` jitted to make its weights where the program places
        them: dense ones by ``dense_shardings``, CSR arrays whole on every
        chip."""
        from repro.launch import serve
        f = functools.partial(fn, cfg_items=lm._hashable(self.cfg), **kw)
        shapes = jax.eval_shape(f, key)
        if "row_ptr" in shapes:
            return jax.jit(f, out_shardings=NamedSharding(self.mesh, P()))
        return jax.jit(f, out_shardings=serve.dense_shardings(shapes,
                                                              self.mesh))

    def setup(self) -> None:
        from repro.core import CSR, PlanPolicy
        from repro.launch import serve
        from repro.models.sparse import SparseLinear
        if self.traffic["loop"] not in ("closed_batch", "pipelined"):
            raise ValueError(f"pruned_cohere has no loop "
                             f"{self.traffic['loop']!r}")
        mesh = self.mesh
        keys = jax.random.split(jax.random.PRNGKey(T.jax_seed(self.seed, 0)),
                                self.dims["layers"] + 1)
        policy = PlanPolicy(with_transpose=False)
        with self.clock.phase("generate"):
            head = jax.block_until_ready(
                self._generator(_head, keys[0])(keys[0]))
            make_dense = self._generator(_dense, keys[1])
            make_mlp = [self._generator(_mlp, keys[1], j=j)
                        for j in range(len(lm._mlp_shapes(self.cfg)))]
        layers, blocks = [], []
        for key in keys[1:]:
            with self.clock.phase("generate"):
                lw = dict(make_dense(key), mlp={})
                for (name, *_), make in zip(lm._mlp_shapes(self.cfg),
                                            make_mlp):
                    # One matrix's sort at a time.
                    lw["mlp"][name] = jax.block_until_ready(make(key))
            with self.clock.phase("plan_build"):
                mlp = {}
                for name, m, kk, _ in lm._mlp_shapes(self.cfg):
                    a = lw["mlp"][name]
                    csr = CSR(a["row_ptr"], a["col_ind"], a["vals"], (m, kk))
                    mlp[name] = SparseLinear(csr, None).shard(
                        mesh, axis=AXIS, policy=policy)
                jax.block_until_ready(mlp)
            layers.append(lw)
            blocks.append({"ln1": {"scale": lw["ln1"]},
                           "attn": lw["attn"], "mlp": mlp})
        self.weights = {"embed": head["embed"],
                        "final_norm": head["final_norm"], "layers": layers}
        self.head, self.blocks = serve.place_on_mesh(
            {"embed": head["embed"],
             "final_norm": {"scale": head["final_norm"]}}, blocks, mesh)
        self.tokens = T.prompts(self.traffic, self.seed, self.dims["vocab"])
        self.prompts = [jax.device_put(p, NamedSharding(mesh, P()))
                        for p in self.tokens]
        fwd = jax.jit(serve.make_pruned_forward(self.mcfg))
        with self.clock.phase("compile"):
            self.compiled = fwd.lower(self.head, self.blocks,
                                      self.prompts[0]).compile()
        with self.clock.phase("warmup"):
            jax.block_until_ready(self.call(0))

    # ------------------------------------------------------- timed path ---

    def call(self, i: int):
        return self.compiled(self.head, self.blocks,
                             self.prompts[i % len(self.prompts)])

    def unit_tokens(self, i: int) -> int:
        return int(self.tokens[i % len(self.tokens)].size)

    def unit_work(self, i: int) -> dict:
        """Required operations and the SpMM calls of call ``i``: each
        global matrix once, whatever the chips it is spread over."""
        b, s = self.tokens[i % len(self.tokens)].shape
        return {"flops": b * work.lm_flops(self.cfg, self.nnz_per_layer, s),
                # pruned_lm's count, which reads only ``cfg`` and ``dims``
                "spmm": lm.System.spmm_calls(self, b * s)}

    # ------------------------------------------------------------ check ---

    def release(self) -> None:
        """Free the program's state (plans, programs); keep the weights
        the benchmark made for the reference."""
        from repro import engine
        for name in ("compiled", "blocks", "head"):
            if hasattr(self, name):
                delattr(self, name)
        engine.clear_cache()
        gc.collect()

    def check(self, samples, quant=None) -> dict:
        """As ``pruned_lm.System.check``, against this family's
        reference."""
        pairs = []
        for i, got in samples:
            tokens = self.tokens[i % len(self.tokens)]
            want = ref.forward(self.weights, tokens, self.cfg, None)
            if quant is not None:
                got = ref.forward(self.weights, tokens, self.cfg, quant)
            pairs.append((jnp.asarray(got).reshape(want.shape), want))
        return lm.compare(pairs)
