"""Model / job configuration system.

A ``ModelConfig`` fully describes one architecture from the assigned pool.
The layer stack is expressed as a repeating ``block_pattern`` of
``(mixer, mlp)`` kind pairs; ``plan_blocks`` expands it into scan groups so
that HLO size stays O(|pattern|) regardless of depth.

Mixer kinds : attn | local | cross | rglru | rwkv
MLP kinds   : mlp  | moe   | cmix  (rwkv channel-mix)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

BlockDef = tuple[str, str]  # (mixer_kind, mlp_kind)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                   # 0 -> d_model // num_heads
    block_pattern: tuple[BlockDef, ...] = (("attn", "mlp"),)
    # --- mlp ---
    mlp_variant: str = "swiglu"         # swiglu | geglu | gelu
    # --- moe ---
    num_experts: int = 0
    experts_per_token: int = 1
    capacity_factor: float = 1.25
    shared_expert: bool = False
    router_aux_coef: float = 0.01
    moe_group_size: int = 1024          # tokens per dispatch group (GLaM-style);
                                        # dispatch einsum FLOPs scale with it
    # --- attention ---
    window_size: int = 0                # for 'local' mixer blocks
    use_qk_norm: bool = False
    fused_softmax: bool = False         # softmax(where=): REFUTED in §Perf
                                        # (+9% bytes on this XLA) — off by default
    softmax_dtype: str = "float32"      # f32 (safe) | bfloat16 (§Perf trade)
    rope_theta: float = 10000.0
    rope_scaling: float = 1.0           # linear RoPE scaling: positions / factor
    pos_embedding: str = "rope"         # rope | learned | none
    max_position: int = 0               # learned pos table size (0 = seq dependent)
    num_media_tokens: int = 0           # vlm patch embeds / audio frames (stub frontend)
    # --- encoder-decoder (audio backbone) ---
    encoder_layers: int = 0
    # --- recurrent ---
    lru_width: int = 0                  # 0 -> d_model
    conv1d_width: int = 4
    rwkv_head_dim: int = 64
    # --- misc ---
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    logits_softcap: float = 0.0
    # --- compute policy ---
    dtype: str = "bfloat16"
    param_dtype: str = "float32"        # optimizer master dtype
    compute_param_dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"          # full | dots | nothing (jax.checkpoint policy)
    microbatch: int = 1                 # gradient-accumulation splits per step
    scan_layers: bool = True
    use_pallas: bool = False
    # --- decode policy ---
    decode_window: int = 0              # >0: sliding-window KV cache for decode
                                        # (enables long_500k on dense archs)
    supports_long_context: bool = True  # False -> skip long_500k (documented)
    # provenance
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def resolved_lru_width(self) -> int:
        return self.lru_width or self.d_model

    @property
    def rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder_layers > 0

    @property
    def uses_media(self) -> bool:
        return self.num_media_tokens > 0

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------------
    def layer_defs(self) -> list[BlockDef]:
        """The full, ordered list of (mixer, mlp) blocks for the decoder."""
        pat = self.block_pattern
        out = []
        for i in range(self.num_layers):
            out.append(pat[i % len(pat)])
        return out

    def plan_blocks(self) -> list[tuple[tuple[BlockDef, ...], int, int]]:
        """Group the stack into scan groups.

        Returns a list of (superblock, repeat, n_layers_covered).  A
        superblock is one full pattern repetition scanned ``repeat`` times;
        a trailing remainder (num_layers % len(pattern)) is emitted as a
        group with repeat == 1 per leftover block.
        """
        pat = self.block_pattern
        k, r = divmod(self.num_layers, len(pat))
        groups: list[tuple[tuple[BlockDef, ...], int, int]] = []
        if k > 0:
            groups.append((pat, k, k * len(pat)))
        for j in range(r):
            groups.append(((pat[j],), 1, 1))
        return groups

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, f, V = self.d_model, self.d_ff, self.vocab_size
        H, KV, hd = self.num_heads, self.num_kv_heads, self.resolved_head_dim
        total = V * d                      # embedding
        total += d                         # final norm
        if not self.tie_embeddings:
            total += V * d
        if self.pos_embedding == "learned" and self.max_position:
            total += self.max_position * d
        if self.encoder_layers:
            total += d + self.num_media_tokens * d  # enc final norm + enc pos
        enc_blocks = [("attn", "mlp")] * self.encoder_layers
        for mixer, mlp in self.layer_defs() + enc_blocks:
            total += 2 * d                 # two pre-norms
            if mixer in ("attn", "local", "cross"):
                total += d * H * hd + 2 * d * KV * hd + H * hd * d
                if self.use_qk_norm:
                    total += 2 * hd
            elif mixer == "rglru":
                w = self.resolved_lru_width
                total += 2 * d * w         # x branch + gate branch
                total += self.conv1d_width * w + w
                total += 2 * w * w + 2 * w  # input/recurrence gates
                total += w                 # log-lambda
                total += w * d             # out proj
            elif mixer == "rwkv":
                total += 5 * d             # token-shift mus (r,k,v,w,g)
                total += 6 * d * d         # r,k,v,g,decay,out projections
                total += 4 * d             # decay_base, u_bonus, ln scale/bias
            if mlp == "mlp":
                n_in = 2 if self.mlp_variant in ("swiglu", "geglu") else 1
                total += n_in * d * f + f * d
            elif mlp == "cmix":
                total += d * f + f * d + d * d + 2 * d
            elif mlp == "moe":
                E = self.num_experts
                total += d * E             # router
                total += E * (2 * d * f + f * d)
                if self.shared_expert:
                    total += 2 * d * f + f * d
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if self.num_experts == 0:
            return self.param_count()
        d, f, E = self.d_model, self.d_ff, self.num_experts
        expert_p = 2 * d * f + f * d
        n_moe = sum(1 for _, m in self.layer_defs() if m == "moe")
        inactive = n_moe * (E - self.experts_per_token) * expert_p
        return self.param_count() - inactive


# ----------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    """One of the four assigned input shapes."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced config of the same family: <=2 pattern repeats, d_model<=512,
    <=4 experts — used by CPU smoke tests."""
    pat_len = len(cfg.block_pattern)
    layers = min(cfg.num_layers, max(pat_len, 2 * pat_len if pat_len <= 3 else pat_len))
    d = min(cfg.d_model, 256)
    hd = 32
    heads = max(1, d // 64)
    kv = max(1, min(cfg.num_kv_heads, heads))
    return cfg.replace(
        num_layers=layers,
        d_model=d,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=hd,
        d_ff=min(cfg.d_ff, 512),
        vocab_size=min(cfg.vocab_size, 512),
        num_experts=min(cfg.num_experts, 4) if cfg.num_experts else 0,
        lru_width=min(cfg.resolved_lru_width, d) if cfg.lru_width or cfg.arch_type in ("hybrid",) else 0,
        rwkv_head_dim=32,
        window_size=min(cfg.window_size, 64) if cfg.window_size else 0,
        num_media_tokens=min(cfg.num_media_tokens, 16) if cfg.num_media_tokens else 0,
        encoder_layers=min(cfg.encoder_layers, 2),
        max_position=min(cfg.max_position, 4096) if cfg.max_position else 0,
        decode_window=min(cfg.decode_window, 64) if cfg.decode_window else 0,
        remat=False,
        dtype="float32",
        compute_param_dtype="float32",
    )
