"""Qwen3-1.7B — dense GQA with per-head QK RMSNorm.

[hf:Qwen/Qwen3-1.7B] 28L d_model=2048 16H (GQA kv=8) d_ff=6144
vocab=151936, head_dim 128, qk_norm.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-1.7b",
    arch_type="dense",
    num_layers=28,
    d_model=2048,
    num_heads=16,
    num_kv_heads=8,
    head_dim=128,
    d_ff=6144,
    vocab_size=151_936,
    block_pattern=(("attn", "mlp"),),
    mlp_variant="swiglu",
    use_qk_norm=True,
    rope_theta=1_000_000.0,
    tie_embeddings=True,
    decode_window=8192,
    supports_long_context=True,
    source="hf:Qwen/Qwen3-1.7B",
)
