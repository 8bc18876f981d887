"""Shared primitive layers: parameter specs, norms, rope, MLPs.

Parameters are described by ``PSpec`` leaves (shape + logical axes + init
kind); the same spec tree drives real init, abstract init (dry-run) and the
logical→mesh sharding rules in ``repro.distributed.sharding``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# ----------------------------------------------------------------------
# Parameter specs


@dataclass(frozen=True)
class PSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]       # logical axis names, len == len(shape)
    init: str = "normal"               # normal | zeros | ones | lru_lambda
    fan_in: int | None = None          # override scale denominator

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pspec(x: Any) -> bool:
    return isinstance(x, PSpec)


def _materialize(spec: PSpec, key: jax.Array, dtype: jnp.dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "lru_lambda":
        # RG-LRU: Λ s.t. a = sigmoid(Λ)^(c·r) starts with |a| in [0.9, 0.999]
        u = jax.random.uniform(key, spec.shape, jnp.float32, 0.9, 0.999)
        lam = jnp.log(u ** (1.0 / 8.0) / (1 - u ** (1.0 / 8.0)))
        return lam.astype(dtype)
    fan_in = spec.fan_in or (spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1])
    scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, spec.shape, jnp.float32) * scale).astype(dtype)


def init_tree(specs, key: jax.Array, dtype) -> Any:
    """Materialize a PSpec tree into real parameters (unique key per leaf)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_pspec)
    keys = jax.random.split(key, len(leaves))
    vals = [_materialize(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_tree(specs, dtype) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs, is_leaf=is_pspec
    )


def axes_tree(specs) -> Any:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_pspec)


def stack_specs(specs, n: int) -> Any:
    """Prepend a scanned 'layers' dim of size n to every leaf spec."""
    return jax.tree.map(
        lambda s: dataclasses.replace(s, shape=(n, *s.shape), axes=("layers", *s.axes)),
        specs,
        is_leaf=is_pspec,
    )


# ----------------------------------------------------------------------
# Activation sharding constraints (Megatron-style), mesh-aware and optional:
# no-ops when no mesh is active or a dim is not divisible.


def constrain(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain activation sharding by logical role per dim.

    Roles: 'batch' -> ('pod','data') prefix that divides, 'model' -> the
    tensor-parallel axis, None -> replicated. Silently skips when the ambient
    mesh lacks the axis or the dim is not divisible.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return x
    if mesh is None or not mesh.axis_names:
        return x
    names = mesh.axis_names
    shape = x.shape
    spec: list = []
    for dim, role in zip(shape, axes):
        entry = None
        if role == "batch":
            chosen, size = [], 1
            for a in ("pod", "data"):
                if a in names and dim % (size * mesh.shape[a]) == 0:
                    chosen.append(a)
                    size *= mesh.shape[a]
            entry = tuple(chosen) if chosen else None
        elif role == "model" and "model" in names and dim % mesh.shape["model"] == 0:
            entry = "model"
        elif role == "data" and "data" in names and dim % mesh.shape["data"] == 0:
            entry = "data"
        spec.append(entry)
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_any(x: jax.Array, *options: tuple) -> jax.Array:
    """Apply the first constraint option whose 'model'-role dims divide.

    Used where the preferred sharding can be impossible for an arch (e.g.
    56 attention heads on a 16-way model axis): fall back to
    sequence-parallel sharding instead of silently replicating O(T^2)
    buffers."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return x
    if mesh is None or not mesh.axis_names or "model" not in mesh.axis_names:
        return x
    msize = mesh.shape["model"]
    for axes in options:
        ok = True
        for dim, role in zip(x.shape, axes):
            if role == "model" and dim % msize != 0:
                ok = False
                break
        if ok:
            return constrain(x, *axes)
    return x


# ----------------------------------------------------------------------
# Norms


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def group_norm_heads(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    """Per-head GroupNorm used by RWKV time-mix output. x: (..., H, K)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_spec(d: int) -> PSpec:
    return PSpec((d,), ("embed",), init="zeros")


# ----------------------------------------------------------------------
# Rotary embeddings


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                scaling: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """positions: (...,) int -> (sin, cos) of shape (..., head_dim//2), f32.

    ``scaling`` is linear RoPE scaling (position interpolation): the angles
    are those of positions / scaling. At 1.0 nothing is divided, so the
    traced computation is that of an unscaled model."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)
    if scaling != 1.0:
        pos = pos / scaling
    ang = pos[..., None] * freq
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, T, H, hd); sin/cos: (T, hd//2) or broadcastable (B, T, hd//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if sin.ndim == 2:  # (T, half) -> (1, T, 1, half)
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:  # (B, T, half) -> (B, T, 1, half)
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(dt)


# ----------------------------------------------------------------------
# MLPs


def mlp_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_variant in ("swiglu", "geglu"):
        return {
            "wi_gate": PSpec((d, f), ("embed", "mlp")),
            "wi_up": PSpec((d, f), ("embed", "mlp")),
            "wo": PSpec((f, d), ("mlp", "embed")),
        }
    return {"wi": PSpec((d, f), ("embed", "mlp")), "wo": PSpec((f, d), ("mlp", "embed"))}


def apply_mlp(cfg, p: dict, x: jax.Array) -> jax.Array:
    if cfg.mlp_variant == "swiglu":
        h = jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    elif cfg.mlp_variant == "geglu":
        h = jax.nn.gelu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    h = constrain(h, "batch", None, "model")
    return h @ p["wo"]


def cmix_specs(cfg) -> dict:
    """RWKV channel-mix (token-shift + squared-relu FFN)."""
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mu_k": PSpec((d,), ("embed",), init="ones"),
        "mu_r": PSpec((d,), ("embed",), init="ones"),
        "wk": PSpec((d, f), ("embed", "mlp")),
        "wr": PSpec((d, d), ("embed", "embed")),
        "wv": PSpec((f, d), ("mlp", "embed")),
    }


def token_shift(x: jax.Array, x_prev_last: jax.Array | None = None) -> jax.Array:
    """Shift sequence right by one. x: (B, T, d). For decode, pass prev token."""
    if x_prev_last is not None:
        return x_prev_last[:, None, :]
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]


def apply_cmix(cfg, p: dict, x: jax.Array, shifted: jax.Array) -> jax.Array:
    xk = x + (shifted - x) * p["mu_k"]
    xr = x + (shifted - x) * p["mu_r"]
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    return jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"])


# ----------------------------------------------------------------------
# Misc


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return jnp.tanh(x / cap) * cap


def cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype) if a.dtype != jnp.int32 else a, tree)


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, T, C), w: (width, C), b: (C,)."""
    width = w.shape[0]
    out = jnp.zeros_like(x)
    for i in range(width):
        tap = w[i][None, None, :]
        if i == 0:
            out = out + x * tap
        else:
            out = out + jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i, :] * tap
    return out + b[None, None, :]


def conv1d_decode(x_t: jax.Array, conv_state: jax.Array, w: jax.Array, b: jax.Array):
    """One-step depthwise causal conv. x_t: (B, C); conv_state: (B, width-1, C)
    holding previous inputs (oldest first). Returns (y_t, new_state)."""
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B, width, C)
    # full-seq form is out_t = sum_i w[i] * x_{t-i}; window is oldest-first,
    # so window[:, j] pairs with tap w[width-1-j].
    y = jnp.einsum("bwc,wc->bc", window, w[::-1]) + b[None, :]
    new_state = window[:, 1:, :]
    return y, new_state


partial = partial  # re-export convenience
