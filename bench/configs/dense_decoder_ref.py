"""Plain float32 reference for a dense decoder-only transformer in training.

The architecture as Qwen3 and Llama-family configs publish it: pre-norm
RMSNorm blocks, grouped-query attention with rotary embeddings (rotate-half
form, base ``rope_theta``), optional per-head RMSNorm on queries and keys
(Qwen3), a SwiGLU MLP, a final RMSNorm and a tied or untied output head,
trained on the mean next-token cross-entropy with AdamW and a global-norm
gradient clip.

It is written in straightforward ``jax.numpy`` and imports nothing of the
program. Every matrix product runs at ``Precision.HIGHEST``, so it is float32
on the TPU as well. It processes one batch row at a time, recomputes each
layer in the backward pass and takes the head's logits in sequence chunks,
so that a training step at the cell's own sizes fits one chip.

Departures from the published model, each shared with the program under
test: weights are random, drawn from ``PRNGKey(0)`` (truncated normal on
[-2, 2] times 1/sqrt(fan_in); norm scales are zero under a ``(1 + scale)``
form, which equals the published ones-initialised scale). The leaf names and
the order in which they draw their keys follow the program's parameter
layout, which is the only way both sides hold the same weights.

``matmul="fp8"`` is the control: every matrix product takes its forward
operands through float8 e4m3 and its backward cotangents through float8
e5m2, each with a per-tensor scale, as fp8 training would.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_CHUNKS = 8          # the head's logits are taken T/8 positions at a time
LAYER_PREFIX = "decoder/0/0/"


# ----------------------------------------------------------------------
# Parameters


def leaf_specs(cfg: dict) -> dict[str, tuple[tuple[int, ...], int | None]]:
    """name -> (shape, fan_in); fan_in None marks a zero-initialised scale.
    Layer leaves are stacked on a leading axis of ``num_hidden_layers``."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    layer = {
        "ln1": ((d,), None),
        "ln2": ((d,), None),
        "mixer/wq": ((d, h, hd), d),
        "mixer/wk": ((d, kv, hd), d),
        "mixer/wv": ((d, kv, hd), d),
        "mixer/wo": ((h, hd, d), h * hd),
        "mlp/wi_gate": ((d, f), d),
        "mlp/wi_up": ((d, f), d),
        "mlp/wo": ((f, d), f),
    }
    if cfg.get("qk_norm"):
        layer["mixer/q_norm"] = ((hd,), None)
        layer["mixer/k_norm"] = ((hd,), None)
    specs = {LAYER_PREFIX + k: ((n, *shape), fan)
             for k, (shape, fan) in layer.items()}
    specs["embed"] = ((v, d), d)
    specs["final_norm"] = ((d,), None)
    if not cfg["tie_word_embeddings"]:
        specs["lm_head"] = ((d, v), d)
    return dict(sorted(specs.items()))


def init_params(cfg: dict, seed: int = 0) -> dict[str, jax.Array]:
    specs = leaf_specs(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    out = {}
    for key, (name, (shape, fan_in)) in zip(keys, specs.items()):
        if fan_in is None:
            out[name] = jnp.zeros(shape, F32)
        else:
            out[name] = jax.random.truncated_normal(
                key, -2.0, 2.0, shape, F32) / np.sqrt(fan_in)
    return out


# ----------------------------------------------------------------------
# Matrix products: float32, or the fp8 control


def _dot_f32(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _qdq(x, dtype):
    """Round x through ``dtype`` with a per-tensor scale to its largest
    finite value."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(F32) / scale


@jax.custom_vjp
def _fwd_e4m3(x):
    return _qdq(x, jnp.float8_e4m3fn)


_fwd_e4m3.defvjp(lambda x: (_qdq(x, jnp.float8_e4m3fn), None),
                 lambda _, g: (g,))


@jax.custom_vjp
def _bwd_e5m2(x):
    return x


_bwd_e5m2.defvjp(lambda x: (x, None),
                 lambda _, g: (_qdq(g, jnp.float8_e5m2),))


def _dot_fp8(spec, a, b):
    return _bwd_e5m2(_dot_f32(spec, _fwd_e4m3(a), _fwd_e4m3(b)))


DOTS = {"f32": _dot_f32, "fp8": _dot_fp8}


# ----------------------------------------------------------------------
# Forward and loss of one row


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (T, heads, hd), rotate-half form."""
    t, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, dot, x, p):
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    h = _rms(x, p["ln1"], eps)
    q = dot("td,dhk->thk", h, p["mixer/wq"])
    k = dot("td,dhk->thk", h, p["mixer/wk"])
    v = dot("td,dhk->thk", h, p["mixer/wv"])
    if cfg.get("qk_norm"):
        q = _rms(q, p["mixer/q_norm"], eps)
        k = _rms(k, p["mixer/k_norm"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = dot("thk,shk->hts", q, k) / math.sqrt(hd)
    t = x.shape[0]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = dot("hts,shk->thk", probs, v)
    x = x + dot("thk,hkd->td", o, p["mixer/wo"])
    h2 = _rms(x, p["ln2"], eps)
    gate = dot("td,df->tf", h2, p["mlp/wi_gate"])
    up = dot("td,df->tf", h2, p["mlp/wi_up"])
    return x + dot("tf,fd->td", jax.nn.silu(gate) * up, p["mlp/wo"])


def row_loss(cfg, dot, params, tokens, labels):
    """Mean next-token cross-entropy of one row; tokens, labels: (T,)."""
    x = params["embed"][tokens]
    layers = {k[len(LAYER_PREFIX):]: v for k, v in params.items()
              if k.startswith(LAYER_PREFIX)}

    @jax.checkpoint
    def body(x, p):
        return _layer(cfg, dot, x, p), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    head = (params["embed"] if cfg["tie_word_embeddings"]
            else params["lm_head"].T)                      # (V, d)
    t = x.shape[0]
    xs = x.reshape(HEAD_CHUNKS, t // HEAD_CHUNKS, -1)
    ls = labels.reshape(HEAD_CHUNKS, t // HEAD_CHUNKS)

    @jax.checkpoint
    def chunk(total, xl):
        xc, lc = xl
        logits = dot("td,vd->tv", xc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), F32), (xs, ls))
    return total / t


# ----------------------------------------------------------------------
# Training steps


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, matmul: str):
    cfg = dict(cfg_items)
    dot = DOTS[matmul]
    grad = jax.jit(jax.value_and_grad(
        functools.partial(row_loss, cfg, dot)))
    add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                  donate_argnums=(0,))
    return grad, add


def _leaf_norms(tree) -> dict[str, float]:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v)))) for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "clip"),
                   donate_argnums=(0, 1, 2))
def _adamw(params, m, v, grads, count, *, lr, b1, b2, eps, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9))
    count = count + 1
    b1c = 1.0 - b1 ** count.astype(F32)
    b2c = 1.0 - b2 ** count.astype(F32)
    new_p, new_m, new_v = {}, {}, {}
    for k, g in grads.items():
        g = g * scale
        new_m[k] = b1 * m[k] + (1 - b1) * g
        new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g)
        new_p[k] = params[k] - lr * (new_m[k] / b1c) / (
            jnp.sqrt(new_v[k] / b2c) + eps)
    return new_p, new_m, new_v, count


def train_steps(cfg: dict, batches: list[dict], opt: dict,
                matmul: str = "f32") -> dict:
    """Run ``len(batches)`` AdamW steps from the seeded initial weights.

    Returns each step's loss (taken before its update), the per-leaf norms
    of step 0's gradient before clipping, and the per-leaf norms of the
    weights' change over all the steps."""
    grad, add = _programs(tuple(sorted(
        (k, v) for k, v in cfg.items() if not isinstance(v, (dict, list)))),
        matmul)
    params = init_params(cfg)
    start = {k: np.asarray(v) for k, v in params.items()}   # host copy
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v = {k: jnp.zeros_like(x) for k, x in params.items()}
    count = jnp.zeros((), jnp.int32)
    losses, grad0 = [], None
    for batch in batches:
        tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        rows = tokens.shape[0]
        acc, loss = None, 0.0
        for r in range(rows):
            row_l, g = grad(params, jnp.asarray(tokens[r]),
                            jnp.asarray(labels[r]))
            loss += float(row_l)
            acc = g if acc is None else add(acc, g)
            del g
        acc = jax.tree.map(lambda a: a / rows, acc)
        losses.append(loss / rows)
        if grad0 is None:
            grad0 = _leaf_norms(acc)
        params, m, v, count = _adamw(
            params, m, v, acc, count, lr=opt["lr"], b1=opt["b1"],
            b2=opt["b2"], eps=opt["eps"], clip=opt["clip"])
        del acc
    change = {k: float(jnp.sqrt(jnp.sum(jnp.square(
        params[k] - jnp.asarray(start[k]))))) for k in params}
    return {"losses": losses, "grad0_norms": grad0, "change_norms": change}
