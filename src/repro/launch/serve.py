"""Serving driver: batched autoregressive decoding behind the TonY job path
(the inference-job flavour of the paper's orchestration).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 8 --gen 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import TonYClient, YarnLikeBackend, job_spec_from_props, make_cluster
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M


def batched_generate(cfg, params, prompts: np.ndarray, gen_len: int,
                     cache_len: int, context=None) -> tuple[np.ndarray, dict]:
    """Greedy decode: prefill via teacher-forced decode steps, then generate."""
    B, P = prompts.shape
    state = M.init_decode_state(cfg, params, B, cache_len, context=context)
    step = jax.jit(lambda p, s, t: M.decode_step(cfg, p, s, t, cache_len))
    toks = jnp.asarray(prompts)
    t0 = time.monotonic()
    logits = None
    for i in range(P):
        logits, state = step(params, state, toks[:, i:i + 1])
    out = []
    cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
    for _ in range(gen_len):
        out.append(cur)
        logits, state = step(params, state, cur)
        cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
    dt = time.monotonic() - t0
    gen = np.asarray(jnp.concatenate(out, axis=1))
    stats = {"tokens_generated": int(B * gen_len),
             "wall_s": dt,
             "tok_per_s": B * (P + gen_len) / dt}
    return gen, stats


def make_serve_program(cfg, *, batch: int, prompt_len: int, gen_len: int,
                       cache_len: int, out_box: dict):
    def program(env, ctx):
        if not ctx.rendezvous(timeout=60.0):
            return 3
        if env["TASK_TYPE"] == "worker" and env["TASK_INDEX"] == "0":
            rng = np.random.default_rng(0)
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            prompts = rng.integers(0, cfg.vocab_size, size=(batch, prompt_len))
            context = None
            if cfg.uses_media or cfg.is_encoder_decoder:
                media = jnp.asarray(rng.normal(
                    size=(batch, cfg.num_media_tokens, cfg.d_model)),
                    jnp.dtype(cfg.dtype))
                context = (M.encode(cfg, params, media)
                           if cfg.is_encoder_decoder else media)
            gen, stats = batched_generate(cfg, params, prompts, gen_len,
                                          cache_len, context)
            out_box["gen"] = gen
            out_box["stats"] = stats
            ctx.shared["train_done"] = True
        else:
            while not ctx.cancel.is_set() and not ctx.shared.get("train_done"):
                time.sleep(0.005)
        ctx.rendezvous(timeout=30.0)
        return 0

    return program


def main(argv: list[str] | None = None) -> int:
    """Runs one job; returns the exit code (0 only when it SUCCEEDED)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cache_len = args.prompt_len + args.gen
    rm = make_cluster(num_gpu_nodes=2, num_cpu_nodes=1, gpus_per_node=4)
    client = TonYClient(YarnLikeBackend(rm))
    job = job_spec_from_props({
        "tony.application.name": f"serve-{cfg.name}",
        "tony.worker.instances": "2",
        "tony.worker.memory": "8192",
        "tony.worker.gpus": "1",
        "tony.worker.node-label": "gpu",
    })
    box: dict = {}
    result = client.run_and_wait(
        job, make_serve_program(cfg, batch=args.batch, prompt_len=args.prompt_len,
                                gen_len=args.gen, cache_len=cache_len,
                                out_box=box))
    print(json.dumps({"status": result.final_status,
                      "stats": box.get("stats"),
                      "sample_tokens": box["gen"][0][:8].tolist()
                      if "gen" in box else None}, indent=2))
    return 0 if result.succeeded else 1


if __name__ == "__main__":
    raise SystemExit(main())
