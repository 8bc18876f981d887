"""Compiles for one described TPU v5e chip, with no chip attached.

The chip's compiler runs here, so these tests catch what interpret mode
cannot: block shapes the chip's tiling refuses, primitives Mosaic cannot
lower, and steps that do not fit the device. Nothing runs. The topology is
described inside a fixture, so only the test process that is given this file
loads the TPU library; keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import to_shardings
from repro.distributed.steps import abstract_train_state, make_train_fn
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_scan import linear_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.wkv import wkv
from repro.optim import AdamWConfig

# Qwen3-1.7B widths at the training sequence length of chip_smoke.py
T, D, H, KV, HD = 2048, 2048, 16, 8, 128
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_rmsnorm_compiles(one_chip):
    compiled = _compile(lambda x, s: rmsnorm(x, s, interpret=False), one_chip,
                        ((T, D), jnp.bfloat16), ((D,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_forward_compiles(one_chip):
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        one_chip, ((1, T, H, HD), jnp.bfloat16), ((1, T, KV, HD), jnp.bfloat16),
        ((1, T, KV, HD), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_train_step_compiles_for_one_chip(topo):
    """One layer at full Qwen3-1.7B widths, B=1, T=2048, on one chip."""
    cfg = get_config("qwen3-1.7b").replace(num_layers=1)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        step, pspecs = make_train_fn(cfg, mesh,
                                     opt=AdamWConfig(weight_decay=0.0))
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            abstract_train_state(cfg), to_shardings(pspecs, mesh))
        tok = jax.ShapeDtypeStruct((1, T), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        compiled = step.lower(state, {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic has no TPU lowering for dynamic_slice, "
                          "which the per-step pl.dslice store needs")
def test_linear_scan_compiles(one_chip):
    # RecurrentGemma-2B widths: lru_width 2560
    _compile(lambda a, b: linear_scan(a, b, interpret=False), one_chip,
             ((1, T, 2560), jnp.float32), ((1, T, 2560), jnp.float32))


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="block (1, bt, 1, 64) breaks the rule that the last "
                          "two block dims divide by 8 and 128 or equal the "
                          "array's")
def test_wkv_compiles(one_chip):
    # RWKV6-3B widths: 40 heads of 64
    x = ((1, T, 40, 64), jnp.float32)
    _compile(lambda r, k, v, w, u: wkv(r, k, v, w, u, interpret=False),
             one_chip, x, x, x, x, ((40, 64), jnp.float32))
