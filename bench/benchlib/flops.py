"""Model FLOPs of one training step of a dense decoder, from its sizes.

Keys are those of the configuration file (Hugging Face names). Recomputation
for activation memory does not count: this is the work the model needs, not
the work the program chose to do.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    projection of every layer plus the output head. The embedding lookup is
    a gather and does not count; a tied head counts once, as the head."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 x matmul parameters (forward 2, backward 4) plus causal attention:
    per layer q.k and p.v are 2 x 2 x H x hd FLOPs per (query, key) pair in
    the forward, over T/2 keys on average under the causal mask, times 3 for
    forward and backward: 6 x T x H x hd per token per layer."""
    attention = 6 * cfg["num_hidden_layers"] * seq_len * (
        cfg["num_attention_heads"] * cfg["head_dim"])
    return 6.0 * matmul_params(cfg) + attention


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> float:
    return train_flops_per_token(cfg, seq_len) * batch * seq_len
