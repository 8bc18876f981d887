import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For one (arch x input-shape x mesh x strategy):
  compile  - lower + compile the FULL config (scan-over-layers), print
             memory_analysis (fits?) and cost_analysis, parse collective
             bytes from optimized HLO.
  analysis - lower UNROLLED reduced-depth variants (1x and 2x the block
             pattern) on the same mesh/shardings and extrapolate exact
             per-layer FLOPs/bytes/collective-bytes to full depth (XLA's
             cost_analysis counts while-loop bodies once, so the scanned
             program under-reports; see EXPERIMENTS.md §Dry-run).

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k \
      [--multi-pod] [--strategy fsdp_tp] [--mode compile|analysis] [--out f.json]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import steps as S
from repro.launch.mesh import make_production_mesh

from repro.launch.dryrun_lib import (  # noqa: E402
    COLLECTIVE_OPS,
    _extrapolate,
    _finalize_terms,
    model_flops,
    parse_collective_bytes,
    rwkv_correction_flops,
    should_skip,
)

# ----------------------------------------------------------------------


def _lower_one(cfg: ModelConfig, shape: ShapeConfig, mesh, strategy: str):
    """Lower + compile one step; returns (compiled, lowered)."""
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn, _ = S.make_train_fn(cfg, mesh, strategy, shape=shape)
            lowered = fn.lower(S.abstract_train_state(cfg),
                               S.train_batch_specs(cfg, shape))
        elif shape.kind == "prefill":
            fn, _ = S.make_prefill_fn(cfg, mesh, strategy, shape=shape)
            from repro.models import abstract_params
            lowered = fn.lower(abstract_params(cfg),
                               S.prefill_batch_specs(cfg, shape))
        else:
            fn, _ = S.make_decode_fn(cfg, mesh, strategy, shape=shape)
            from repro.models import abstract_params
            lowered = fn.lower(abstract_params(cfg),
                               S.decode_state_specs(cfg, shape),
                               S.decode_token_specs(shape))
        compiled = lowered.compile()
    return compiled, lowered


def _extract(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": None if ma is None else {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "alias_bytes_per_device": ma.alias_size_in_bytes,
        },
    }


def _coerce(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v in ("true", "True"):
        return True
    if v in ("false", "False"):
        return False
    return v


def run_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
               strategy: str = "fsdp_tp", mode: str = "compile",
               overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = INPUT_SHAPES[shape_name]
    result = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "strategy": strategy, "mode": mode,
              "overrides": overrides or {},
              "model_flops": model_flops(cfg, shape),
              "active_params": cfg.active_param_count(),
              "total_params": cfg.param_count()}
    skip = should_skip(cfg, shape)
    if skip:
        result.update(ok=True, skipped=skip)
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        if mode == "compile":
            compiled, _ = _lower_one(cfg, shape, mesh, strategy)
            result["full"] = _extract(compiled)
            result["note"] = ("scan-over-layers program: cost_analysis counts "
                              "loop bodies once; use analysis mode for exact "
                              "roofline terms")
        else:
            pat = len(cfg.block_pattern)
            enc = cfg.encoder_layers
            if cfg.num_layers <= 12:
                c_ex = cfg.replace(scan_layers=False)
                compiled, _ = _lower_one(c_ex, shape, mesh, strategy)
                ex = _extract(compiled)
                ex["exact"] = True
                result["extrapolated"] = _finalize_terms(ex, cfg, shape)
                result["samples"] = {"exact": ex}
            else:
                c1 = cfg.replace(num_layers=pat, scan_layers=False)
                c2 = cfg.replace(num_layers=2 * pat, scan_layers=False)
                e1 = _extract(_lower_one(c1, shape, mesh, strategy)[0])
                e2 = _extract(_lower_one(c2, shape, mesh, strategy)[0])
                reps = cfg.num_layers / pat
                ext = _extrapolate(e1, e2, reps)
                result["extrapolated"] = _finalize_terms(ext, cfg, shape)
                result["samples"] = {"x1": e1, "x2": e2, "reps": reps}
        result["ok"] = True
        result["elapsed_s"] = time.time() - t0
    except Exception as e:  # noqa: BLE001
        result.update(ok=False, error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:],
                      elapsed_s=time.time() - t0)
    return result




def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="fsdp_tp")
    ap.add_argument("--mode", default="compile", choices=["compile", "analysis"])
    ap.add_argument("--out", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable), e.g. "
                         "--set fused_softmax=false --set remat=false")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = _coerce(v)
    res = run_dryrun(args.arch, args.shape, multi_pod=args.multi_pod,
                     strategy=args.strategy, mode=args.mode,
                     overrides=overrides or None)
    text = json.dumps(res, indent=2, default=str)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
