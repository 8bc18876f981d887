"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The single-pod production mesh is 16x16 = 256
chips (one TPU v5e pod); multi-pod is 2x16x16 = 512 chips with a leading
'pod' axis (DCN boundary).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_parallel: int | None = None) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (smoke tests, examples)."""
    n = len(jax.devices())
    if model_parallel is None:
        model_parallel = 1
        for m in (4, 2, 1):
            if n % m == 0:
                model_parallel = m
                break
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


# Hardware constants (TPU v5e target) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
