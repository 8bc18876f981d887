"""Logical-axis -> mesh-axis sharding rules.

Strategies (the TonY worker/ps story mapped onto GSPMD):

  fsdp_tp   - production default: parameters shard FSDP-style over the data
              axes (embed dim) and tensor/expert-parallel over the model axis.
  ps        - paper-faithful parameter-server strategy: every parameter is
              sharded across the 'model' axis only ("ps shards"); workers
              (data axis) run pure data-parallel compute, so XLA materializes
              the PS pull/push as all-gather / reduce-scatter on that axis.
  allreduce - replicated parameters, batch sharded over every mesh axis
              (classic synchronous all-reduce data parallelism).

A rule maps a logical axis name to a priority list of mesh-axis candidates;
the first candidate whose size divides the dim (and whose axes are still
unused in that param) wins, otherwise the dim is replicated.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# candidate entries are mesh-axis names or tuples of them
RULES: dict[str, dict[str, list]] = {
    "fsdp_tp": {
        "rwkv_out": ["model"],
        "vocab": ["model"],
        "embed": [("pod", "data"), "data"],
        "mlp": ["model"],
        "heads": ["model"],
        "kv_heads": ["model"],
        "experts": ["model"],
        "lru": ["model"],
        "layers": [],
        "head_dim": [],
        "conv": [],
    },
    "ps": {
        # every param's first shardable dim lands on the PS ("model") axis
        "rwkv_out": ["model"],
        "vocab": ["model"],
        "embed": ["model"],
        "mlp": ["model"],
        "heads": ["model"],
        "kv_heads": ["model"],
        "experts": ["model"],
        "lru": ["model"],
        "layers": [],
        "head_dim": [],
        "conv": [],
    },
    "allreduce": {k: [] for k in
                  ["vocab", "embed", "mlp", "heads", "kv_heads", "experts",
                   "lru", "layers", "head_dim", "conv", "rwkv_out"]},
    # Megatron-style: tensor-parallel over 'model', params REPLICATED across
    # the data axis (no FSDP gathers; gradients all-reduce over data only).
    "tp_only": {
        "rwkv_out": ["model"],
        "vocab": ["model"],
        "embed": [],
        "mlp": ["model"],
        "heads": ["model"],
        "kv_heads": ["model"],
        "experts": ["model"],
        "lru": ["model"],
        "layers": [],
        "head_dim": [],
        "conv": [],
    },
}
STRATEGIES = tuple(RULES)


def _mesh_axis_size(mesh: Mesh, entry) -> int:
    if isinstance(entry, tuple):
        return int(np.prod([mesh.shape[a] for a in entry]))
    return mesh.shape[entry]


def _axes_of(entry) -> tuple[str, ...]:
    return entry if isinstance(entry, tuple) else (entry,)


def spec_for(axes: tuple, shape: tuple, mesh: Mesh, rules: dict[str, list],
             max_shardings: int | None = None) -> P:
    """Build a PartitionSpec for one param given its logical axes."""
    used: set[str] = set()
    out = []
    n_assigned = 0
    for ax_name, dim in zip(axes, shape):
        assigned = None
        if ax_name is not None and (max_shardings is None or n_assigned < max_shardings):
            for cand in rules.get(ax_name, []):
                cand_axes = _axes_of(cand)
                if any(a not in mesh.shape for a in cand_axes):
                    continue
                if used & set(cand_axes):
                    continue
                if dim % _mesh_axis_size(mesh, cand) == 0 and dim > 0:
                    assigned = cand
                    used.update(cand_axes)
                    n_assigned += 1
                    break
        out.append(assigned)
    return P(*out)


def param_pspecs(cfg, mesh: Mesh, strategy: str = "fsdp_tp"):
    """PartitionSpec tree aligned with init_params(cfg)."""
    from repro.models.layers import is_pspec
    from repro.models.model import build_specs

    rules = RULES[strategy]
    max_sh = 1 if strategy == "ps" else None
    return jax.tree.map(
        lambda s: spec_for(s.axes, s.shape, mesh, rules, max_shardings=max_sh),
        build_specs(cfg), is_leaf=is_pspec)


def _batch_axes(mesh: Mesh, global_batch: int):
    """Pick the widest prefix of ('pod','data','model') that divides batch.
    'model' participates only when every mesh axis is needed (allreduce)."""
    cands = [a for a in ("pod", "data") if a in mesh.shape]
    chosen: list[str] = []
    size = 1
    for a in cands:
        if global_batch % (size * mesh.shape[a]) == 0:
            chosen.append(a)
            size *= mesh.shape[a]
    return tuple(chosen), size


def batch_pspecs(mesh: Mesh, global_batch: int, strategy: str = "fsdp_tp",
                 include_model: bool = False) -> P:
    axes, size = _batch_axes(mesh, global_batch)
    if (strategy == "allreduce" or include_model) and "model" in mesh.shape:
        if global_batch % (size * mesh.shape["model"]) == 0:
            axes = (*axes, "model")
    return P(axes if axes else None)


def to_shardings(tree_pspecs, mesh: Mesh):
    return jax.tree.map(lambda p: NamedSharding(mesh, p), tree_pspecs,
                        is_leaf=lambda x: isinstance(x, P))
