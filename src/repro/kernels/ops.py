"""Jit'd public wrappers for the Pallas kernels.

The kernels run compiled on an accelerator and in the Pallas interpreter on
the CPU backend (where they are validated against ``kernels/ref.py``). The
mode follows ``jax.default_backend()`` at call time; there is no switch.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.linear_scan import linear_scan as _linear_scan
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.wkv import wkv as _wkv


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=_interpret())


def linear_scan(a, b, *, block_t=128, block_c=128):
    return _linear_scan(a, b, block_t=block_t, block_c=block_c,
                        interpret=_interpret())


def wkv(r, k, v, log_w, u, *, block_t=64):
    return _wkv(r, k, v, log_w, u, block_t=block_t, interpret=_interpret())


def rmsnorm(x, scale, *, eps=1e-6, block_rows=128):
    return _rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                    interpret=_interpret())
