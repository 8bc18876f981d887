"""GQA attention: full / local(sliding-window) / cross variants, full-sequence
and single-token KV-cache decode paths (linear + ring-buffer caches).

The jnp implementation here is the reference path; ``cfg.use_pallas`` routes
full-sequence self-attention through the Pallas flash kernel (TPU target,
interpret-validated on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (PSpec, apply_rope, constrain,
                                 constrain_any, rms_norm, rope_angles)

NEG_INF = -2.0e38


def attn_specs(cfg, cross: bool = False) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": PSpec((d, H, hd), ("embed", "heads", "head_dim"), fan_in=d),
        "wk": PSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wv": PSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wo": PSpec((H, hd, d), ("heads", "head_dim", "embed"), fan_in=H * hd),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = PSpec((hd,), ("head_dim",), init="zeros")
        p["k_norm"] = PSpec((hd,), ("head_dim",), init="zeros")
    return p


def _gqa_scores(q, k):
    """q: (B,Tq,H,hd), k: (B,Tk,KV,hd) -> scores (B,H,Tq,Tk).

    Q-head-major layout: the O(T^2) score buffer carries the full H dim so it
    shards over the 'model' axis even when num_kv_heads < model-axis size
    (GQA kv=8 on a 16-way TP mesh would otherwise replicate it)."""
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    kr = jnp.repeat(k, H // KV, axis=2)          # (B,Tk,H,hd)
    return jnp.einsum("bthd,bshd->bhts", q, kr)


def _gqa_out(probs, v):
    """probs: (B,H,Tq,Tk), v: (B,Tk,KV,hd) -> (B,Tq,H,hd)."""
    B, H, Tq, _ = probs.shape
    KV = v.shape[2]
    vr = jnp.repeat(v, H // KV, axis=2)          # (B,Tk,H,hd)
    return jnp.einsum("bhts,bshd->bthd", probs, vr)


def masked_softmax(scores: jax.Array, mask: jax.Array | None,
                   fused: bool = True,
                   softmax_dtype: str = "float32") -> jax.Array:
    """fused=True (§Perf): softmax(where=) masks inside the reduction — one
    fewer materialized (B,H,T,S) f32 buffer than the where()+softmax form
    (jax's where-softmax already zeroes masked positions).
    softmax_dtype='bfloat16' keeps the scores buffer half-width (§Perf
    accuracy/memory trade, default f32)."""
    s = scores.astype(jnp.dtype(softmax_dtype))
    if mask is None:
        return jax.nn.softmax(s, axis=-1)
    if fused:
        return jax.nn.softmax(s, axis=-1, where=mask)
    s = jnp.where(mask, s, NEG_INF)
    return jax.nn.softmax(s, axis=-1)


def make_mask(Tq: int, Tk: int, *, causal: bool, window: int, q_offset=0):
    """(Tq, Tk) boolean mask built from iotas (no O(T^2) host tensor)."""
    if not causal and window <= 0:
        return None
    qpos = jnp.arange(Tq)[:, None] + q_offset
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    return mask


def self_attention(cfg, p, x, *, causal: bool, window: int, positions=None):
    """Full-sequence self attention. x: (B,T,d)."""
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        pos = positions if positions is not None else jnp.arange(T)
        sin, cos = rope_angles(pos, hd, cfg.rope_theta, cfg.rope_scaling)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    q = constrain_any(q, ("batch", None, "model", None),
                      ("batch", "model", None, None))
    if cfg.use_pallas and causal and cfg.pos_embedding != "learned":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window)
    else:
        scores = _gqa_scores(q, k) / jnp.sqrt(hd).astype(jnp.float32)
        scores = constrain_any(scores, ("batch", "model", None, None),
                               ("batch", None, "model", None))
        mask = make_mask(T, T, causal=causal, window=window)
        probs = masked_softmax(scores, mask, cfg.fused_softmax,
                               cfg.softmax_dtype).astype(q.dtype)
        out = _gqa_out(probs, v)
    out = constrain_any(out, ("batch", None, "model", None),
                        ("batch", "model", None, None))
    return jnp.einsum("bthk,hkd->btd", out, p["wo"])


def cross_attention(cfg, p, x, media_kv):
    """x: (B,T,d); media_kv: precomputed (k, v) each (B,M,KV,hd)."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k, v = media_kv
    q = constrain_any(q, ("batch", None, "model", None),
                      ("batch", "model", None, None))
    scores = _gqa_scores(q, k) / jnp.sqrt(hd).astype(jnp.float32)
    scores = constrain_any(scores, ("batch", "model", None, None),
                           ("batch", None, "model", None))
    probs = masked_softmax(scores, None, cfg.fused_softmax,
                           cfg.softmax_dtype).astype(q.dtype)
    out = _gqa_out(probs, v)
    return jnp.einsum("bthk,hkd->btd", out, p["wo"])


def media_kv(cfg, p, media):
    """Precompute cross-attention K/V from media embeddings (B,M,d)."""
    k = jnp.einsum("bmd,dhk->bmhk", media, p["wk"])
    v = jnp.einsum("bmd,dhk->bmhk", media, p["wv"])
    if cfg.use_qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v


# ----------------------------------------------------------------------
# Decode (single new token, KV cache)


def init_cache(cfg, batch: int, capacity: int, dtype) -> dict:
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, capacity, KV, hd), dtype),
        "v": jnp.zeros((batch, capacity, KV, hd), dtype),
    }


def decode_self_attention(cfg, p, x_t, cache, pos, *, window: int):
    """One-token decode. x_t: (B,1,d); cache k/v: (B,C,KV,hd); pos: scalar.

    When ``window > 0`` (or capacity < full seq) the cache is a ring buffer:
    slot = pos % C.  Returns (out (B,1,d), new_cache).
    """
    B = x_t.shape[0]
    hd = cfg.resolved_head_dim
    C = cache["k"].shape[1]
    q = jnp.einsum("btd,dhk->bthk", x_t, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x_t, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x_t, p["wv"])
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embedding == "rope":
        sin, cos = rope_angles(pos[None], hd, cfg.rope_theta,
                               cfg.rope_scaling)  # (1, hd/2)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)  # rotated at true position before caching
    slot = jnp.mod(pos, C)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    # slot i holds absolute position p_i = pos - ((pos - i) mod C)
    idx = jnp.arange(C)
    slot_pos = pos - jnp.mod(pos - idx, C)
    valid = slot_pos >= 0
    if window > 0:
        valid &= slot_pos > pos - window
    scores = _gqa_scores(q, ck) / jnp.sqrt(hd).astype(jnp.float32)
    probs = masked_softmax(scores, valid[None, None, None, :],
                           cfg.fused_softmax, cfg.softmax_dtype).astype(q.dtype)
    out = _gqa_out(probs, cv)
    out = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return out, {"k": ck, "v": cv}


def decode_cross_attention(cfg, p, x_t, media_cache):
    """Cross-attn during decode against precomputed media K/V."""
    return cross_attention(cfg, p, x_t, (media_cache["k"], media_cache["v"]))
