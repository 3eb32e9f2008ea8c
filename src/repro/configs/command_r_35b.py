"""command-r-35b [dense] — full multi-head attention, no bias, parallel
blocks, weight-only LayerNorm, interleaved RoPE, scaled logits
[hf:CohereForAI/c4ai-command-r-v01 config.json, model_type cohere]."""
import dataclasses

from .base import ModelConfig

CONFIG = ModelConfig(
    name="command-r-35b",
    family="dense",
    num_layers=40,
    d_model=8192,
    num_heads=64,
    num_kv_heads=64,
    d_ff=22528,
    vocab_size=256000,
    norm="layernorm",
    norm_eps=1e-5,
    norm_bias=False,
    parallel_block=True,
    tie_embeddings=True,
    rope_theta=8_000_000.0,
    rope_interleaved=True,
    logit_scale=0.0625,
)


def smoke_config() -> ModelConfig:
    return dataclasses.replace(
        CONFIG, num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=0, d_ff=128, vocab_size=512, segments=())
