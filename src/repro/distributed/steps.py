"""The jitted distributed train step.

``make_train_fn`` returns the jitted AdamW step and the PartitionSpec tree of
its state; ``init_train_state`` builds that state and ``abstract_train_state``
its shapes without allocating.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import sharding as sh
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init, adamw_update


def batch_shardings(cfg, shape, mesh, strategy) -> dict:
    bspec = sh.batch_pspecs(mesh, shape.global_batch, strategy)
    out = {"tokens": P(*bspec, None), "labels": P(*bspec, None)}
    if cfg.is_encoder_decoder:
        out["frames"] = P(*bspec, None, None)
    elif cfg.uses_media:
        out["media"] = P(*bspec, None, None)
    return out


def make_train_fn(cfg: ModelConfig, mesh: Mesh, strategy: str = "fsdp_tp",
                  opt: AdamWConfig | None = None, shape: ShapeConfig | None = None):
    opt = opt or AdamWConfig()
    p_specs = sh.param_pspecs(cfg, mesh, strategy)
    state_pspecs = {
        "params": p_specs,
        "opt": {"m": p_specs, "v": p_specs, "count": P()},
        "step": P(),
    }

    def train_step(state, batch):
        k = max(cfg.microbatch, 1)
        if k == 1:
            def lf(params):
                return M.loss_fn(cfg, params, batch)

            (_, metrics), grads = jax.value_and_grad(lf, has_aux=True)(state["params"])
        else:
            # gradient accumulation: k sequential microbatches; activation
            # residency /k at the cost of k-fold weight re-gathers (§Perf)
            mb = jax.tree.map(
                lambda a: a.reshape(k, a.shape[0] // k, *a.shape[1:]), batch)

            def micro(carry, one):
                gsum, lsum = carry
                (_, m), g = jax.value_and_grad(
                    lambda p: M.loss_fn(cfg, p, one), has_aux=True)(state["params"])
                return (jax.tree.map(jnp.add, gsum, g),
                        jax.tree.map(jnp.add, lsum, m)), None

            zeros_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            zeros_m = {"loss": 0.0, "ce": 0.0, "moe_aux": 0.0, "router_z": 0.0}
            zeros_m = jax.tree.map(jnp.float32, zeros_m)
            (grads, msum), _ = jax.lax.scan(micro, (zeros_g, zeros_m), mb)
            grads = jax.tree.map(lambda g: g / k, grads)
            metrics = jax.tree.map(lambda m: m / k, msum)
        new_params, new_opt, opt_metrics = adamw_update(
            opt, grads, state["opt"], state["params"])
        metrics = {**metrics, **opt_metrics}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    state_sh = sh.to_shardings(state_pspecs, mesh)
    batch_sh = (None if shape is None else sh.to_shardings(
        batch_shardings(cfg, shape, mesh, strategy), mesh))
    jitted = jax.jit(
        train_step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
    return jitted, state_pspecs


def init_train_state(cfg: ModelConfig, rng: jax.Array) -> dict:
    params = M.init_params(cfg, rng)
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(cfg: ModelConfig) -> dict:
    params = M.abstract_params(cfg)
    opt = {"m": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params),
           "v": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params),
           "count": jax.ShapeDtypeStruct((), jnp.int32)}
    return {"params": params, "opt": opt,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}

