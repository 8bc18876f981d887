"""Plain float32 reference for a dense decoder in training, its state sharded
over the run's chips.

The training math of ``dense_decoder_ref.py``, whose docstring holds the
architecture, the departures shared with the program under test and the fp8
control, and from which this file takes its parameter layout, its matrix
products, its norm and its AdamW step. Two things are added:

* linear RoPE scaling: where the configuration file gives ``rope_scaling``
  of type ``linear``, the positions are divided by its ``factor`` before the
  angles are taken (position interpolation, as Hugging Face's Llama does);
* a layout over the chips. At DeepSeek-Coder-33B widths, four layers with
  the embedding and the untied head are 2.58 B parameters: the float32
  weights, AdamW's two moments, the step's gradient and one row's gradient
  come to 51.7 GB, which no chip holds. So each of them lies over a one-axis
  mesh of the run's devices (``jax.devices()``) with ``NamedSharding``:
  ``wq`` and ``wo`` split on the query heads, ``wk`` and ``wv`` on the kv
  heads, the MLP's three matrices on its width, the embedding and the head
  on the vocabulary; the norm scales, a row's activations and its tokens are
  replicated. The math is written for whole arrays and XLA partitions it:
  the layout changes where a number is computed, not what is computed.

As in ``dense_decoder_ref.py`` every matrix product runs at
``Precision.HIGHEST``, one batch row at a time, each layer recomputed in the
backward pass and the head's logits taken in sequence chunks. Each row's
gradient is added into the step's gradient inside the program that takes it,
starting from zeros (0 + g is g); the initial weights are drawn again at the
end to take the weights' change, rather than kept in host memory.
"""
from __future__ import annotations

import functools
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from benchlib.spec import load_module

plain = load_module(Path(__file__).with_name("dense_decoder_ref.py"),
                    "dense_decoder_ref")
F32 = plain.F32
LAYER = plain.LAYER_PREFIX
AXIS = "model"
# where each leaf is split over the mesh: (axis of the stacked leaf, the
# size it splits); every other leaf is replicated
SPLIT = {
    LAYER + "mixer/wq": (2, "num_attention_heads"),
    LAYER + "mixer/wk": (2, "num_key_value_heads"),
    LAYER + "mixer/wv": (2, "num_key_value_heads"),
    LAYER + "mixer/wo": (1, "num_attention_heads"),
    LAYER + "mlp/wi_gate": (2, "intermediate_size"),
    LAYER + "mlp/wi_up": (2, "intermediate_size"),
    LAYER + "mlp/wo": (1, "intermediate_size"),
    "embed": (0, "vocab_size"),
    "lm_head": (1, "vocab_size"),
}


# ----------------------------------------------------------------------
# Layout


def mesh_for(cfg: dict) -> Mesh:
    """The most of the run's devices that divide every split."""
    devices = jax.devices()
    sizes = {cfg[key] for _, key in SPLIT.values()}
    n = max(k for k in range(1, len(devices) + 1)
            if all(s % k == 0 for s in sizes))
    return Mesh(np.array(devices[:n]), (AXIS,))


def shardings(cfg: dict, mesh: Mesh) -> dict[str, NamedSharding]:
    out = {}
    for name, (shape, _) in plain.leaf_specs(cfg).items():
        spec = [None] * len(shape)
        if name in SPLIT:
            spec[SPLIT[name][0]] = AXIS
        out[name] = NamedSharding(mesh, P(*spec))
    return out


def init_params(cfg: dict, mesh: Mesh, seed: int = 0) -> dict[str, jax.Array]:
    """``dense_decoder_ref.init_params``, drawn straight into the shards."""
    return jax.jit(functools.partial(plain.init_params, cfg, seed),
                   out_shardings=shardings(cfg, mesh))()


# ----------------------------------------------------------------------
# Forward and loss of one row


def rope_factor(cfg: dict) -> float:
    """The published linear scaling factor; 1 where the file gives none."""
    scaling = cfg.get("rope_scaling") or {}
    kind = scaling.get("type", scaling.get("rope_type", "linear"))
    if kind != "linear":
        raise ValueError(f"only linear RoPE scaling is written here: {scaling}")
    return float(scaling.get("factor", 1.0))


def _rope(x, theta, factor):
    """x: (T, heads, hd), rotate-half form, at positions / factor."""
    t, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = (jnp.arange(t, dtype=F32) / factor)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, factor, dot, x, p):
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    rms = plain._rms
    h = rms(x, p["ln1"], eps)
    q = dot("td,dhk->thk", h, p["mixer/wq"])
    k = dot("td,dhk->thk", h, p["mixer/wk"])
    v = dot("td,dhk->thk", h, p["mixer/wv"])
    if cfg.get("qk_norm"):
        q = rms(q, p["mixer/q_norm"], eps)
        k = rms(k, p["mixer/k_norm"], eps)
    q = _rope(q, cfg["rope_theta"], factor)
    k = _rope(k, cfg["rope_theta"], factor)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = dot("thk,shk->hts", q, k) / math.sqrt(hd)
    t = x.shape[0]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = dot("hts,shk->thk", probs, v)
    x = x + dot("thk,hkd->td", o, p["mixer/wo"])
    h2 = rms(x, p["ln2"], eps)
    gate = dot("td,df->tf", h2, p["mlp/wi_gate"])
    up = dot("td,df->tf", h2, p["mlp/wi_up"])
    return x + dot("tf,fd->td", jax.nn.silu(gate) * up, p["mlp/wo"])


def row_loss(cfg, factor, dot, params, tokens, labels):
    """Mean next-token cross-entropy of one row; tokens, labels: (T,)."""
    x = params["embed"][tokens]
    layers = {k[len(LAYER):]: v for k, v in params.items()
              if k.startswith(LAYER)}

    @jax.checkpoint
    def body(x, p):
        return _layer(cfg, factor, dot, x, p), None

    x, _ = jax.lax.scan(body, x, layers)
    x = plain._rms(x, params["final_norm"], cfg["rms_norm_eps"])
    head = (params["embed"] if cfg["tie_word_embeddings"]
            else params["lm_head"].T)                      # (V, d)
    t = x.shape[0]
    xs = x.reshape(plain.HEAD_CHUNKS, t // plain.HEAD_CHUNKS, -1)
    ls = labels.reshape(plain.HEAD_CHUNKS, t // plain.HEAD_CHUNKS)

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
def _programs(cfg_items: tuple, factor: float, matmul: str, mesh: Mesh):
    cfg = dict(cfg_items)
    shard = shardings(cfg, mesh)
    one = NamedSharding(mesh, P())
    value_and_grad = jax.value_and_grad(
        functools.partial(row_loss, cfg, factor, plain.DOTS[matmul]))

    def grad_add(acc, params, tokens, labels):
        """One row's loss, and the step's gradient with the row's added."""
        loss, g = value_and_grad(params, tokens, labels)
        return loss, jax.tree.map(jnp.add, acc, g)

    grad_add = jax.jit(grad_add, out_shardings=(one, shard),
                       donate_argnums=(0,))
    mean = jax.jit(lambda acc, rows: jax.tree.map(lambda a: a / rows, acc),
                   out_shardings=shard, donate_argnums=(0,))
    adamw = jax.jit(plain._adamw.__wrapped__,
                    static_argnames=("lr", "b1", "b2", "eps", "clip"),
                    out_shardings=(shard, shard, shard, one),
                    donate_argnums=(0, 1, 2))
    return grad_add, mean, adamw


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


def train_steps(cfg: dict, batches: list[dict], opt: dict,
                matmul: str = "f32") -> dict:
    """Run ``len(batches)`` AdamW steps from the seeded initial weights.

    Returns each step's loss (taken before its update), the per-leaf norms
    of step 0's gradient before clipping, and the per-leaf norms of the
    weights' change over all the steps."""
    mesh = mesh_for(cfg)
    one = NamedSharding(mesh, P())
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if not isinstance(v, (dict, list))))
    grad_add, mean, adamw = _programs(items, rope_factor(cfg), matmul, mesh)
    params = init_params(cfg, mesh)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    losses, grad0 = [], None
    for batch in batches:
        tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        rows = tokens.shape[0]
        acc, loss = jax.tree.map(jnp.zeros_like, params), 0.0
        for r in range(rows):
            row_l, acc = grad_add(acc, params, jax.device_put(tokens[r], one),
                                  jax.device_put(labels[r], one))
            loss += float(row_l)
        acc = mean(acc, rows)
        losses.append(loss / rows)
        if grad0 is None:
            grad0 = plain._leaf_norms(acc)
        params, m, v, count = adamw(
            params, m, v, acc, count, lr=opt["lr"], b1=opt["b1"],
            b2=opt["b2"], eps=opt["eps"], clip=opt["clip"])
        del acc
    del m, v
    start = init_params(cfg, mesh)
    change = {k: float(_diff_norm(params[k], start[k])) for k in params}
    return {"losses": losses, "grad0_norms": grad0, "change_norms": change}
