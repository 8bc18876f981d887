"""Flash attention Pallas TPU kernel: online-softmax tiling with GQA, causal
and sliding-window masking.

TPU adaptation: the GPU algorithm's warp-level softmax turns
into MXU-aligned (block_q x block_k) tiles streamed HBM->VMEM; the running
(m, l, acc) state lives in VMEM scratch and persists across the sequential
innermost grid dimension (TPU grids iterate in order, which replaces the GPU
thread-block loop).

Grid: (B, H, num_q_blocks, num_k_blocks), k innermost. Operands are taken
head-major, so each block ends in (block, hd) as the chip's (8, 128) tiling
rule wants; query head h reads kv head h // (H // KV) (GQA).
Blocks: q, o (1, 1, bq, hd) | k, v (1, 1, bk, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, num_k_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :].astype(jnp.float32)        # (bq, hd)
    k = k_ref[0, 0, :, :].astype(jnp.float32)        # (bk, hd)
    v = v_ref[0, 0, :, :].astype(jnp.float32)        # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                              # (bq, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked running max
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[...] / safe_l
        out = jnp.where(l == 0.0, 0.0, out)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = True):
    """q: (B,T,H,hd); k,v: (B,S,KV,hd) -> (B,T,H,hd).

    Differentiable: custom_vjp with the Pallas kernel forward and the exact
    reference-math backward (Pallas interpret mode has no JVP rule; on real
    TPU the backward would be a second kernel with the same tiling)."""
    return _flash_vjp(q, k, v, causal, window, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash_impl(q, k, v, causal=causal, window=window, block_q=block_q,
                       block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    out = _flash_impl(q, k, v, causal=causal, window=window, block_q=block_q,
                      block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    from repro.kernels.ref import flash_attention_ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(q_, k_, v_, causal=causal,
                                               window=window), q, k, v)
    return vjp(g)


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def _flash_impl(q, k, v, *, causal: bool, window: int, block_q: int,
                block_k: int, interpret: bool):
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    G = H // KV
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    nq, nk = T // bq, S // bk
    # head-major, so every block ends in (block, hd): the chip's tiling rule
    qh = q.transpose(0, 2, 1, 3)                     # (B, H, T, hd)
    kh = k.transpose(0, 2, 1, 3)                     # (B, KV, S, hd)
    vh = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (hd ** 0.5), causal=causal, window=window,
        block_q=bq, block_k=bk, num_k_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),        # running max m
            pltpu.VMEM((bq, 1), jnp.float32),        # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),       # accumulator
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
