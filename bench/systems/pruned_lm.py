"""A decoder with pruned MLP matrices, run through the program.

Set-up makes every weight on the device in one jitted call from the seed:
the embedding and attention matrices dense, and each MLP matrix directly
as the CSR a deployment would load, with a uniformly random ``keep``
share of each row's entries kept (what magnitude pruning keeps of iid
weights) and values drawn from the tails that pruning keeps.  The
program then builds its plans (``SparseLinear.with_plan``), compiles its
forward (``repro.launch.serve.make_pruned_forward``) at the shape the
cell's traffic sends, and warms it.

The check compares the logits the timed path produced with the plain
reference (``bench/references/pruned_lm.py``) run on the same weights:
the widest gap by which the program's greedy token at a position lies
below the reference's best logit there, and the relative RMS error of
the logits.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as T
from bench import work
from bench.references import pruned_lm as ref

KEEP_KEY = "keep_per_row"


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    return dict(d=d, ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"],
                hd=d // cfg["num_attention_heads"])


def _mlp_shapes(cfg: dict):
    """(name, rows, cols, init scale) of each MLP matrix, stored as the
    program stores it: ``(d_out, d_in)``."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return (("w1", ff, d, d ** -0.5), ("w3", ff, d, d ** -0.5),
            ("w2", d, ff, ff ** -0.5))


def _pruned_csr(key, m, k, keep, scale):
    """CSR of an (m, k) matrix with ``round(keep * k)`` entries kept per
    row at uniformly random columns (sorted).  Kept values are the ones
    magnitude pruning keeps of a normal weight: |z| beyond the (1 - keep)
    quantile of |N(0, 1)|, random sign, times ``scale``."""
    kr = max(1, int(round(keep * k)))
    ku, kv, ks = jax.random.split(key, 3)
    u = jax.random.uniform(ku, (m, k))
    cols = jnp.sort(jnp.argsort(u, axis=1)[:, :kr].astype(jnp.int32), 1)
    # |z| with P(|Z| > |z|) = keep * p, from the lower tail (finite and
    # exact for small p, where 1 - keep * p / 2 would round to 1).
    p = jax.random.uniform(kv, (m, kr), minval=1e-20, maxval=1.0)
    mag = -jax.scipy.special.ndtri(0.5 * keep * p)
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (m, kr)), 1.0, -1.0)
    return {"row_ptr": jnp.arange(m + 1, dtype=jnp.int32) * kr,
            "col_ind": cols.reshape(-1),
            "vals": (sign * mag * scale).reshape(-1).astype(jnp.float32)}


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _make_weights(key, cfg_items):
    cfg = dict(cfg_items)
    dm = _dims(cfg)
    d, hd = dm["d"], dm["hd"]
    ks = jax.random.split(key, dm["layers"] + 1)
    s = d ** -0.5
    out = {"embed": jax.random.normal(ks[0], (dm["vocab"], d)) * s,
           "final_norm": jnp.ones((d,), jnp.float32), "layers": []}
    for li in range(dm["layers"]):
        k = jax.random.split(ks[li + 1], 7)
        attn = {"wq": jax.random.normal(k[0], (d, dm["heads"] * hd)) * s,
                "wk": jax.random.normal(k[1], (d, dm["kv"] * hd)) * s,
                "wv": jax.random.normal(k[2], (d, dm["kv"] * hd)) * s,
                "wo": jax.random.normal(k[3], (dm["heads"] * hd, d)) * s}
        mlp = {name: _pruned_csr(k[4 + j], m, kk, cfg[KEEP_KEY], sc)
               for j, (name, m, kk, sc) in enumerate(_mlp_shapes(cfg))}
        out["layers"].append({"ln1": jnp.ones((d,), jnp.float32),
                              "attn": attn,
                              "ln2": jnp.ones((d,), jnp.float32),
                              "mlp": mlp})
    return out


def _hashable(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def compare(pairs) -> dict:
    """Numbers over (program logits, reference logits) pairs; a number
    that is not finite reads as infinite, which fails any limit."""
    gap, num, den = 0.0, 0.0, 0.0
    for got, want in pairs:
        v = want.shape[-1]
        got = jnp.asarray(got, jnp.float32).reshape(-1, v)
        want = jnp.asarray(want, jnp.float32).reshape(-1, v)
        pick = jnp.take_along_axis(want, jnp.argmax(got, -1)[:, None], 1)
        g = float(jnp.max(want.max(-1) - pick[:, 0]))
        gap = g if not np.isfinite(g) else max(gap, g)
        num += float(jnp.sum(jnp.square(got - want)))
        den += float(jnp.sum(jnp.square(want)))
    out = {"logit_gap": gap, "logits_rms": (num / den) ** 0.5 if den
           else float("inf")}
    return {k: v if np.isfinite(v) else float("inf")
            for k, v in out.items()}


class System:
    """One pruned decoder under one traffic mix."""

    # The control: the reference one precision step below bfloat16.
    CONTROL = "fp8"

    def __init__(self, cfg: dict, traffic: dict, seed: int, clock,
                 devices):
        self.cfg, self.traffic, self.seed, self.clock = cfg, traffic, seed, \
            clock
        # One chip, the default device, where set-up keeps all the state.
        if list(devices) != jax.devices()[:1]:
            raise ValueError(f"runs on the default device alone, not on "
                             f"{list(devices)}")
        self.devices = devices
        self.dims = _dims(cfg)
        self.nnz_per_layer = sum(
            m * max(1, int(round(cfg[KEEP_KEY] * kk)))
            for _, m, kk, _ in _mlp_shapes(cfg))

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
            tie_embeddings=True, rope_theta=float(c["rope_theta"]),
            param_dtype=c["torch_dtype"], compute_dtype=c["compute_dtype"])

    def setup(self) -> None:
        from repro.core import CSR, PlanPolicy
        from repro.launch import serve
        from repro.models.sparse import SparseLinear
        if self.traffic["loop"] not in ("closed_batch", "pipelined"):
            raise ValueError(f"pruned_lm has no loop "
                             f"{self.traffic['loop']!r}")
        key = jax.random.PRNGKey(T.jax_seed(self.seed, 0))
        with self.clock.phase("generate"):
            self.weights = jax.block_until_ready(
                _make_weights(key, _hashable(self.cfg)))
        policy = PlanPolicy(with_transpose=False)
        w = self.weights
        with self.clock.phase("plan_build"):
            blocks = []
            for lw in w["layers"]:
                mlp = {}
                for name, m, kk, _ in _mlp_shapes(self.cfg):
                    a = lw["mlp"][name]
                    csr = CSR(a["row_ptr"], a["col_ind"], a["vals"], (m, kk))
                    mlp[name] = SparseLinear(csr, None).with_plan(
                        policy=policy)
                blocks.append({"ln1": {"scale": lw["ln1"]},
                               "attn": lw["attn"],
                               "ln2": {"scale": lw["ln2"]}, "mlp": mlp})
            jax.block_until_ready(blocks)
        self.blocks = blocks
        self.head = {"embed": w["embed"],
                     "final_norm": {"scale": w["final_norm"]}}
        self.prompts = [jnp.asarray(p) for p in T.prompts(
            self.traffic, self.seed, self.dims["vocab"])]
        fwd = jax.jit(serve.make_pruned_forward(self.model_config()))
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
        return int(self.prompts[i % len(self.prompts)].size)

    def unit_work(self, i: int) -> dict:
        """Required operations and the SpMM calls of call ``i``."""
        b, s = self.prompts[i % len(self.prompts)].shape
        return {"flops": b * work.lm_flops(self.cfg, self.nnz_per_layer, s),
                "spmm": self.spmm_calls(b * s)}

    def spmm_calls(self, n: int) -> list:
        """The SpMM calls of one forward over ``n`` token columns:
        bfloat16 activations in and out, float32 values."""
        calls = []
        for _ in range(self.dims["layers"]):
            for _, m, kk, _ in _mlp_shapes(self.cfg):
                kr = max(1, int(round(self.cfg[KEEP_KEY] * kk)))
                calls.append(work.SpmmCall(m=m, k=kk, nnz=m * kr, n=n,
                                           val_bytes=4, b_bytes=2,
                                           c_bytes=2))
        return calls

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
        """``samples`` are ``(i, logits)`` pairs the timed path produced
        for call ``i``.  With ``quant`` the reference at that lower
        precision stands in for the program (the control)."""
        pairs = []
        for i, got in samples:
            tokens = np.asarray(self.prompts[i % len(self.prompts)])
            want = ref.forward(self.weights, tokens, self.cfg, None)
            if quant is not None:
                got = ref.forward(self.weights, tokens, self.cfg, quant)
            pairs.append((jnp.asarray(got).reshape(want.shape), want))
        return compare(pairs)
