"""TonY paper-native workload: the kind of model LinkedIn ran through TonY in
2019 — a modest dense network trained with the parameter-server strategy
(TensorFlow-on-YARN era).  We keep it as a small dense transformer so the
same substrate serves it; what makes it "paper-native" is the *job shape*
(worker/ps heterogeneous containers, PS distribution strategy), exercised by
examples/quickstart.py and the end-to-end tests in tests/test_system.py.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="tony-paper-mlp",
    arch_type="dense",
    num_layers=4,
    d_model=256,
    num_heads=4,
    num_kv_heads=4,
    head_dim=64,
    d_ff=1024,
    vocab_size=8192,
    block_pattern=(("attn", "mlp"),),
    mlp_variant="gelu",
    pos_embedding="learned",
    max_position=4096,
    tie_embeddings=True,
    remat=False,
    dtype="float32",
    compute_param_dtype="float32",
    source="OpML'19 TonY (paper-native job)",
)
