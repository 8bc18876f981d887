"""Composable transformer stack covering all assigned architecture families.

The decoder is a sequence of *scan groups* derived from
``ModelConfig.plan_blocks()``: each group is one full ``block_pattern``
repetition whose parameters are stacked on a leading 'layers' axis and
iterated with ``lax.scan`` (HLO size O(|pattern|), not O(depth)).

Public API:
  init_params / abstract_params / logical_axes
  forward(cfg, params, tokens, context)        - full-seq (train / prefill)
  loss_fn(cfg, params, batch)                  - CE + MoE aux losses
  init_decode_state / decode_step              - single-token KV-cache decode
  encode(cfg, params, frames)                  - enc-dec (audio) encoder
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.layers import (
    PSpec,
    abstract_tree,
    apply_cmix,
    apply_mlp,
    axes_tree,
    cmix_specs,
    constrain,
    init_tree,
    mlp_specs,
    norm_spec,
    rms_norm,
    softcap,
    stack_specs,
    token_shift,
)

ZERO_AUX = lambda: {"moe_aux": jnp.zeros((), jnp.float32),  # noqa: E731
                    "router_z": jnp.zeros((), jnp.float32)}


# ----------------------------------------------------------------------
# Parameter specs


def _block_specs(cfg: ModelConfig, bdef) -> dict:
    mixer, mlpk = bdef
    p = {"ln1": norm_spec(cfg.d_model), "ln2": norm_spec(cfg.d_model)}
    if mixer in ("attn", "local", "cross"):
        p["mixer"] = attn.attn_specs(cfg, cross=(mixer == "cross"))
    elif mixer == "rglru":
        p["mixer"] = rec.rglru_specs(cfg)
    elif mixer == "rwkv":
        p["mixer"] = rec.rwkv_specs(cfg)
    else:
        raise ValueError(mixer)
    if mlpk == "mlp":
        p["mlp"] = mlp_specs(cfg)
    elif mlpk == "moe":
        p["mlp"] = moe_mod.moe_specs(cfg)
    elif mlpk == "cmix":
        p["mlp"] = cmix_specs(cfg)
    else:
        raise ValueError(mlpk)
    return p


def _group_specs(cfg: ModelConfig, superblock, repeat: int):
    block_list = tuple(_block_specs(cfg, b) for b in superblock)
    if repeat == 1:
        return block_list
    return tuple(stack_specs(b, repeat) for b in block_list)


def build_specs(cfg: ModelConfig) -> dict:
    d, V = cfg.d_model, cfg.vocab_size
    specs: dict = {
        "embed": PSpec((V, d), ("vocab", "embed"), fan_in=d),
        "decoder": [
            _group_specs(cfg, sb, rep) for sb, rep, _ in cfg.plan_blocks()
        ],
        "final_norm": norm_spec(d),
    }
    if cfg.pos_embedding == "learned":
        specs["pos_table"] = PSpec((cfg.max_position, d), (None, "embed"), fan_in=d)
    if not cfg.tie_embeddings:
        specs["lm_head"] = PSpec((d, V), ("embed", "vocab"), fan_in=d)
    if cfg.is_encoder_decoder:
        enc_pat = (("attn", "mlp"),)
        specs["encoder"] = [_group_specs(cfg, enc_pat, cfg.encoder_layers)]
        specs["enc_final_norm"] = norm_spec(d)
        specs["enc_pos_table"] = PSpec((cfg.num_media_tokens, d), (None, "embed"), fan_in=d)
    return specs


def init_params(cfg: ModelConfig, rng: jax.Array) -> dict:
    return init_tree(build_specs(cfg), rng, jnp.dtype(cfg.param_dtype))


def abstract_params(cfg: ModelConfig, dtype=None) -> dict:
    return abstract_tree(build_specs(cfg), jnp.dtype(dtype or cfg.param_dtype))


def logical_axes(cfg: ModelConfig) -> dict:
    return axes_tree(build_specs(cfg))


# ----------------------------------------------------------------------
# Block application (full sequence)


def _apply_block(cfg, bdef, p, x, context, aux, *, causal=True):
    mixer, mlpk = bdef
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if mixer == "attn":
        y = attn.self_attention(cfg, p["mixer"], h, causal=causal, window=0)
    elif mixer == "local":
        y = attn.self_attention(cfg, p["mixer"], h, causal=causal, window=cfg.window_size)
    elif mixer == "cross":
        kv = attn.media_kv(cfg, p["mixer"], context)
        y = attn.cross_attention(cfg, p["mixer"], h, kv)
    elif mixer == "rglru":
        y = rec.apply_rglru(cfg, p["mixer"], h)
    elif mixer == "rwkv":
        y = rec.apply_rwkv(cfg, p["mixer"], h)
    x = x + y
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if mlpk == "mlp":
        x = x + apply_mlp(cfg, p["mlp"], h2)
    elif mlpk == "moe":
        y, a = moe_mod.apply_moe(cfg, p["mlp"], h2)
        aux = {k: aux[k] + a[k] for k in aux}
        x = x + y
    elif mlpk == "cmix":
        x = x + apply_cmix(cfg, p["mlp"], h2, token_shift(h2))
    return x, aux


def _unstack(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _remat(cfg, fn):
    """jax.checkpoint with the configured save policy (§Perf lever):
    'full' recomputes everything (min memory), 'dots' saves matmul outputs
    (less recompute, more residency), 'nothing' disables remat."""
    if not cfg.remat or cfg.remat_policy == "nothing":
        return fn
    policy = None
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, policy=policy)


def _run_groups(cfg, groups_params, plan, x, context, *, causal=True):
    aux = ZERO_AUX()
    for (superblock, repeat, _), gp in zip(plan, groups_params):
        if repeat == 1:
            for bdef, bp in zip(superblock, gp):
                x, aux = _apply_block(cfg, bdef, bp, x, context, aux, causal=causal)
        elif not cfg.scan_layers:
            # unrolled: exact per-layer HLO (used by the dry-run analysis mode
            # because XLA cost_analysis counts while-loop bodies once); remat
            # still applies per superblock so recompute FLOPs stay faithful
            def one_rep(carry, bps, superblock=superblock):
                xx, ax = carry
                for bdef, bp in zip(superblock, bps):
                    xx, ax = _apply_block(cfg, bdef, bp, xx, context, ax,
                                          causal=causal)
                return xx, ax

            one_rep = _remat(cfg, one_rep)
            for i in range(repeat):
                x, aux = one_rep((x, aux), _unstack(gp, i))
        else:
            def body(carry, xs, superblock=superblock):
                xx, ax = carry
                for bdef, bp in zip(superblock, xs):
                    xx, ax = _apply_block(cfg, bdef, bp, xx, context, ax, causal=causal)
                return (xx, ax), None

            body = _remat(cfg, body)
            (x, aux), _ = jax.lax.scan(body, (x, aux), gp)
    return x, aux


# ----------------------------------------------------------------------
# Full-sequence forward


def _embed(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.pos_embedding == "learned":
        T = tokens.shape[1]
        pos = params["pos_table"][:T].astype(x.dtype)
        x = x + pos[None]
    return x


def _logits(cfg, params, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(x.dtype))
    else:
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(x.dtype))
    logits = constrain(logits, "batch", None, "model")
    return softcap(logits.astype(jnp.float32), cfg.logits_softcap)


def encode(cfg: ModelConfig, params: dict, frames: jax.Array) -> jax.Array:
    """Audio encoder over stub frame embeddings (B, M, d)."""
    x = frames.astype(jnp.dtype(cfg.dtype)) + params["enc_pos_table"][None].astype(
        jnp.dtype(cfg.dtype))
    plan = [((("attn", "mlp"),), cfg.encoder_layers, cfg.encoder_layers)]
    x, _ = _run_groups(cfg, params["encoder"], plan, x, None, causal=False)
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def _resolve_context(cfg, params, batch):
    if cfg.is_encoder_decoder:
        return encode(cfg, params, batch["frames"])
    if cfg.uses_media:
        return batch["media"].astype(jnp.dtype(cfg.dtype))
    return None


def forward(cfg: ModelConfig, params: dict, batch: dict):
    """batch: {'tokens': (B,T) int32, ['media'|'frames']: (B,M,d)}.
    Returns (logits (B,T,V) f32, aux)."""
    compute_params = jax.tree.map(
        lambda a: a.astype(jnp.dtype(cfg.compute_param_dtype))
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    context = _resolve_context(cfg, compute_params, batch)
    x = _embed(cfg, compute_params, batch["tokens"])
    x, aux = _run_groups(cfg, compute_params["decoder"], cfg.plan_blocks(), x, context)
    return _logits(cfg, compute_params, x), aux


def loss_fn(cfg: ModelConfig, params: dict, batch: dict):
    logits, aux = forward(cfg, params, batch)
    labels = batch["labels"]
    # vocab-sharding-friendly CE: no gather along the (model-sharded) V dim —
    # the label logit is extracted with an iota mask so V stays sharded and
    # only (B,T)-shaped partial reductions cross the mesh.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.squeeze(m, -1) + jnp.log(
        jnp.sum(jnp.exp(logits - m), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    label_logit = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
    ll = label_logit - lse
    ce = -jnp.mean(ll)
    loss = ce + cfg.router_aux_coef * aux["moe_aux"] + 1e-3 * aux["router_z"]
    metrics = {"loss": loss, "ce": ce, "moe_aux": aux["moe_aux"],
               "router_z": aux["router_z"]}
    return loss, metrics


# ----------------------------------------------------------------------
# Decode


def _attn_capacity(cfg, mixer, cache_len):
    if mixer == "local":
        return min(cfg.window_size, cache_len)
    if cfg.decode_window and cache_len > cfg.decode_window:
        return cfg.decode_window
    return cache_len


def _attn_window(cfg, mixer, cache_len):
    if mixer == "local":
        return cfg.window_size
    if cfg.decode_window and cache_len > cfg.decode_window:
        return cfg.decode_window
    return 0


def _block_cache(cfg, bdef, batch, cache_len, dtype):
    mixer, mlpk = bdef
    c: dict = {}
    if mixer in ("attn", "local"):
        c["kv"] = attn.init_cache(cfg, batch, _attn_capacity(cfg, mixer, cache_len), dtype)
    elif mixer == "rglru":
        c["rec"] = rec.rglru_init_state(cfg, batch)
    elif mixer == "rwkv":
        c["rec"] = rec.rwkv_init_state(cfg, batch)
    if mlpk == "cmix":
        c["cmix_prev"] = rec.cmix_init_state(cfg, batch)
    return c


def init_decode_state(cfg: ModelConfig, params, batch_size: int, cache_len: int,
                      context: jax.Array | None = None) -> dict:
    """Build the decode state pytree (caches stacked to match scan groups).

    ``context``: media embeddings (VLM) or encoder output (audio); cross-attn
    K/V are precomputed here once and reused every step.
    """
    dtype = jnp.dtype(cfg.dtype)
    layers = []
    for (superblock, repeat, _) in cfg.plan_blocks():
        entries = []
        for bdef in superblock:
            c = _block_cache(cfg, bdef, batch_size, cache_len, dtype)
            if repeat > 1:
                c = jax.tree.map(lambda a: jnp.broadcast_to(a, (repeat, *a.shape)), c)
            entries.append(c)
        layers.append(tuple(entries))

    ctx_kv = None
    if context is not None:
        compute_params = jax.tree.map(
            lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
            params)
        ctx_kv = []
        for (superblock, repeat, _), gp in zip(cfg.plan_blocks(), compute_params["decoder"]):
            entries = []
            for bdef, bp in zip(superblock, gp):
                if bdef[0] != "cross":
                    entries.append(None)
                elif repeat == 1:
                    k, v = attn.media_kv(cfg, bp["mixer"], context)
                    entries.append({"k": k, "v": v})
                else:
                    k, v = jax.vmap(
                        lambda m, ctx=context: attn.media_kv(cfg, m, ctx))(bp["mixer"])
                    entries.append({"k": k, "v": v})
            ctx_kv.append(tuple(entries))
    return {"pos": jnp.zeros((), jnp.int32), "layers": layers, "ctx_kv": ctx_kv}


def _decode_block(cfg, bdef, p, x, cache, ctx, pos, cache_len):
    mixer, mlpk = bdef
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = dict(cache)
    if mixer in ("attn", "local"):
        y, kv = attn.decode_self_attention(
            cfg, p["mixer"], h, cache["kv"], pos,
            window=_attn_window(cfg, mixer, cache_len))
        new_cache["kv"] = kv
    elif mixer == "cross":
        y = attn.decode_cross_attention(cfg, p["mixer"], h, ctx)
    elif mixer == "rglru":
        y, st = rec.decode_rglru(cfg, p["mixer"], h, cache["rec"])
        new_cache["rec"] = st
    elif mixer == "rwkv":
        y, st = rec.decode_rwkv(cfg, p["mixer"], h, cache["rec"])
        new_cache["rec"] = st
    x = x + y
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if mlpk == "mlp":
        x = x + apply_mlp(cfg, p["mlp"], h2)
    elif mlpk == "moe":
        y, _ = moe_mod.apply_moe(cfg, p["mlp"], h2)
        x = x + y
    elif mlpk == "cmix":
        shifted = token_shift(h2, cache["cmix_prev"].astype(h2.dtype))
        x = x + apply_cmix(cfg, p["mlp"], h2, shifted)
        new_cache["cmix_prev"] = h2[:, 0, :].astype(jnp.float32)
    return x, new_cache


def decode_step(cfg: ModelConfig, params: dict, state: dict, tokens: jax.Array,
                cache_len: int):
    """tokens: (B, 1) int32 -> (logits (B,1,V) f32, new_state)."""
    dtype = jnp.dtype(cfg.dtype)
    compute_params = jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params)
    pos = state["pos"]
    x = jnp.take(compute_params["embed"], tokens, axis=0)
    if cfg.pos_embedding == "learned":
        x = x + compute_params["pos_table"][pos][None, None, :].astype(x.dtype)

    new_layers = []
    for gi, ((superblock, repeat, _), gp, gc) in enumerate(
            zip(cfg.plan_blocks(), compute_params["decoder"], state["layers"])):
        ctx_entries = state["ctx_kv"][gi] if state["ctx_kv"] is not None else [None] * len(superblock)
        if repeat == 1:
            entries = []
            for bdef, bp, bc, ctx in zip(superblock, gp, gc, ctx_entries):
                x, nc = _decode_block(cfg, bdef, bp, x, bc, ctx, pos, cache_len)
                entries.append(nc)
            new_layers.append(tuple(entries))
        elif not cfg.scan_layers:
            new_entries = [[] for _ in superblock]
            for i in range(repeat):
                for j, (bdef, bp, bc) in enumerate(zip(superblock, gp, gc)):
                    ctx = ctx_entries[j]
                    ctx_i = _unstack(ctx, i) if isinstance(ctx, dict) else None
                    x, nc = _decode_block(cfg, bdef, _unstack(bp, i), x,
                                          _unstack(bc, i), ctx_i, pos, cache_len)
                    new_entries[j].append(nc)
            stacked = tuple(
                jax.tree.map(lambda *xs: jnp.stack(xs), *entries)
                for entries in new_entries)
            new_layers.append(stacked)
        else:
            def body(xx, xs, superblock=superblock):
                bps, bcs, ctxs = xs
                ncs = []
                for bdef, bp, bc, ctx in zip(superblock, bps, bcs, ctxs):
                    xx, nc = _decode_block(cfg, bdef, bp, xx, bc, ctx, pos, cache_len)
                    ncs.append(nc)
                return xx, tuple(ncs)

            ctxs = tuple(
                c if c is not None else jnp.zeros((repeat,), dtype)
                for c in ctx_entries)
            x, new_gc = jax.lax.scan(body, x, (gp, gc, ctxs))
            new_layers.append(new_gc)
    logits = _logits(cfg, compute_params, x)
    new_state = {"pos": pos + 1, "layers": new_layers, "ctx_kv": state["ctx_kv"]}
    return logits, new_state

