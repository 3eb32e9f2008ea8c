"""Config system: model architecture + run shapes.

Every assigned architecture gets a ``configs/<id>.py`` exporting
``CONFIG`` (exact published numbers) and ``smoke_config()`` (reduced
same-family variant for CPU tests).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int              # query heads (0 for attention-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 → d_model // num_heads

    # --- block structure -------------------------------------------------
    # segments: ((pattern, repeat), ...) where pattern is a tuple of block
    # types from {"attn", "moe", "ssd", "rglru"}; "attn" blocks carry an MLP,
    # per standard pre-norm transformer blocks.
    segments: tuple[tuple[tuple[str, ...], int], ...] = ()

    # --- attention --------------------------------------------------------
    attention: str = "full"     # full | swa | local
    window: int = 4096
    qkv_bias: bool = False
    attn_logit_softcap: float = 0.0

    # --- MoE ----------------------------------------------------------------
    num_experts: int = 0
    top_k: int = 0
    moe_impl: str = "sort"      # sort (merge-based, paper) | dense (einsum)
    moe_groups: int = 0         # >1: hierarchical (per-shard) dispatch

    # --- SSM (mamba2) -----------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64

    # --- RG-LRU (recurrentgemma) -------------------------------------------
    lru_width: int = 0          # 0 → d_model
    conv_width: int = 4

    # --- embeddings / io ----------------------------------------------------
    input_mode: str = "tokens"  # tokens | embeddings (audio/vlm stub frontend)
    tie_embeddings: bool = True
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    norm_eps: float = 1e-6
    norm_bias: bool = True      # layernorm's bias (False: weight-only)
    rope_theta: float = 10_000.0  # 0 → sinusoidal absolute positions
    # rotary pairs (x[2i], x[2i+1]) (GPT-J / Cohere) instead of rotate-half
    rope_interleaved: bool = False
    logit_softcap: float = 0.0
    logit_scale: float = 1.0      # logits multiplied by this (Cohere)
    parallel_block: bool = False  # command-r style parallel attn+FFN
    embed_scale: bool = False     # multiply embeddings by sqrt(d)
    mlp: str = "swiglu"           # swiglu | gelu

    # --- paper technique ----------------------------------------------------
    ffn_prune: float = 0.0      # >0: serve FFN via CSR SpMM, keep fraction

    # --- numerics -----------------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    # --- distribution ----------------------------------------------------
    # layout of the (b, s, d) residual stream between blocks:
    #   ("dp", None, None)     — batch-sharded, d replicated (TP classic)
    #   ("dp", "model", None)  — + sequence-parallel over the model axis
    #   ("dpm", None, None)    — pure-FSDP: batch over every device
    residual_spec: Tuple = ("dp", None, None)
    # False → no tensor parallelism: internal activations follow the batch
    # (pure ZeRO-3 data parallel; used with param_mode="fsdp2")
    tp: bool = True

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)
        if not self.segments:
            object.__setattr__(self, "segments",
                               ((("attn",), self.num_layers),))
        n = sum(len(p) * r for p, r in self.segments)
        assert n == self.num_layers, \
            f"segments cover {n} layers, config says {self.num_layers}"

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def block_types(self):
        out = []
        for pattern, reps in self.segments:
            out += list(pattern) * reps
        return out

    def param_count(self) -> int:
        """Approximate parameter count N (for MODEL_FLOPS = 6·N·D)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.head_dim
        n = v * d  # embed
        if not self.tie_embeddings:
            n += v * d
        for bt in self.block_types():
            if bt == "attn":
                n += d * (self.num_heads * hd)
                n += 2 * d * (self.num_kv_heads * hd)
                n += (self.num_heads * hd) * d
                if ff:
                    n += 3 * d * ff  # SwiGLU
            elif bt == "moe":
                n += d * (self.num_heads * hd)
                n += 2 * d * (self.num_kv_heads * hd)
                n += (self.num_heads * hd) * d
                n += self.num_experts * 3 * d * ff + d * self.num_experts
            elif bt == "ssd":
                din = self.ssm_expand * d
                heads = din // self.ssm_head_dim
                n += d * (2 * din + 2 * self.ssm_state + heads) + din * d
            elif bt == "rglru":
                w = self.lru_width or d
                n += 2 * d * w + w * d + 3 * w
        return n

    def active_param_count(self) -> int:
        """N_active for MoE (top_k of num_experts)."""
        if self.num_experts == 0:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        dense_experts = 0
        for bt in self.block_types():
            if bt == "moe":
                dense_experts += (self.num_experts - self.top_k) * 3 * d * ff
        return self.param_count() - dense_experts


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str               # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    seq_len: int = 2048
    global_batch: int = 8
    microbatches: int = 1        # gradient accumulation steps
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    remat: bool = True
    seed: int = 0
    # distributed-optimization tricks
    grad_compression: str = "none"   # none | int8_ef
    loss_chunk: int = 512            # vocab-chunked CE sequence chunk
