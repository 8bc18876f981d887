"""Training / serving throughput micro-benchmarks (CPU smoke scale) — the ML
side of the jobs TonY orchestrates.

  PYTHONPATH=src python -m benchmarks.training [--json BENCH_training.json]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.data import SyntheticLMDataset
from repro.distributed.steps import init_train_state, make_train_fn
from repro.launch.mesh import make_local_mesh
from repro.models import model as M


def bench_train_step() -> list[tuple[str, float, str]]:
    cfg = get_config("tony-paper-mlp")
    B, T = 8, 128
    mesh = make_local_mesh()
    data = SyntheticLMDataset(B, T, cfg.vocab_size)
    with jax.set_mesh(mesh):
        fn, _ = make_train_fn(cfg, mesh, "fsdp_tp",
                              shape=ShapeConfig("b", T, B, "train"))
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        state, _ = fn(state, batch)  # compile
        jax.block_until_ready(state["params"])
        n = 5
        t0 = time.monotonic()
        for _ in range(n):
            state, m = fn(state, batch)
        jax.block_until_ready(m["loss"])
        dt = (time.monotonic() - t0) / n
    return [("train_step_paper_mlp", dt * 1e6,
             f"{B*T/dt:.0f} tok/s params={cfg.param_count()/1e6:.1f}M")]


def bench_decode_step() -> list[tuple[str, float, str]]:
    cfg = get_smoke_config("qwen3-1.7b")
    B, C = 4, 64
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    state = M.init_decode_state(cfg, params, B, C)
    step = jax.jit(lambda p, s, t: M.decode_step(cfg, p, s, t, C))
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, state = step(params, state, tok)  # compile
    jax.block_until_ready(logits)
    n = 20
    t0 = time.monotonic()
    for _ in range(n):
        logits, state = step(params, state, tok)
    jax.block_until_ready(logits)
    dt = (time.monotonic() - t0) / n
    return [("decode_step_qwen3_smoke", dt * 1e6, f"{B/dt:.0f} tok/s")]


def bench_kernels() -> list[tuple[str, float, str]]:
    from repro.kernels import ops, ref
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    for name, fn in [("flash_attention_interp",
                      lambda: ops.flash_attention(q, k, v, causal=True)),
                     ("attention_ref",
                      lambda: ref.flash_attention_ref(q, k, v, causal=True))]:
        fn()  # compile
        t0 = time.monotonic()
        for _ in range(3):
            out = fn()
        jax.block_until_ready(out)
        rows.append((name, (time.monotonic() - t0) / 3 * 1e6,
                     "interpret-mode (correctness path)"))
    return rows


def all_benches() -> list[tuple[str, float, str]]:
    return bench_train_step() + bench_decode_step() + bench_kernels()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="accepted for CI symmetry (these benches already "
                         "run at smoke scale)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write rows as a JSON benchmark artifact")
    args = ap.parse_args()
    rows = all_benches()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suite": "training", "smoke": args.smoke,
                       "rows": [{"name": n, "us_per_call": round(us, 1),
                                 "derived": d} for n, us, d in rows]},
                      f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
