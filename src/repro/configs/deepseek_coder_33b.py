"""DeepSeek-Coder 33B — dense llama-arch code model.

[arXiv:2401.14196] 62L d_model=7168 56H (GQA kv=8) d_ff=19200 vocab=32256,
untied head, rope_theta 1e5 with linear RoPE scaling, factor 4, over
16,384 positions (the published config.json).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-coder-33b",
    arch_type="dense",
    num_layers=62,
    d_model=7168,
    num_heads=56,
    num_kv_heads=8,
    head_dim=128,
    d_ff=19_200,
    vocab_size=32_256,
    block_pattern=(("attn", "mlp"),),
    mlp_variant="swiglu",
    rope_theta=100_000.0,
    rope_scaling=4.0,
    tie_embeddings=False,
    decode_window=8192,
    supports_long_context=True,
    source="https://huggingface.co/deepseek-ai/deepseek-coder-33b-base/blob/main/config.json",
)
