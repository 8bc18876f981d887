"""A tiny deepseek-coder-33b.train-4chip on four devices, in a process of its
own: the readings ``test_bench_deepseek.py`` checks, as one JSON line.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python bench/tests/deepseek_tiny.py

A test process already holds a backend with one CPU device, so the four
devices need a process that sets ``XLA_FLAGS`` before JAX starts. Every size
is the configuration's own except these widths, cut so that the CPU holds
them while each split of the four-device layout stays even and the query
heads keep DeepSeek-Coder's seven per kv head; the program computes in
float32, so a sound run agrees with the reference to round-off.
"""
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "deepseek-coder-33b.train-4chip"
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=28, num_key_value_heads=4, head_dim=16,
            vocab_size=256)
SEED = 2**31 + 15          # a seed above 32 signed bits


def tiny_cell():
    from benchlib.spec import load_cell

    cell = load_cell(CELL)
    return dataclasses.replace(cell, config=dict(cell.config, **TINY),
                               traffic=dict(cell.traffic, seq_len=32))


def tiny_program_config(config: dict, **kw):
    from repro.configs import get_config

    return get_config(config["program_config"]).replace(
        num_layers=TINY["num_hidden_layers"], d_model=TINY["hidden_size"],
        num_heads=TINY["num_attention_heads"],
        num_kv_heads=TINY["num_key_value_heads"], head_dim=TINY["head_dim"],
        d_ff=TINY["intermediate_size"], vocab_size=TINY["vocab_size"],
        dtype="float32", compute_param_dtype="float32", **kw)


def mlp_without_exchange(cfg, p, x):
    """``layers.apply_mlp`` with the all-reduce of its row-parallel down
    projection left out: each device keeps the partial sum over its own
    quarter of d_ff and goes on with it, as a chip would whose exchange was
    dropped."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.models.layers import constrain

    h = jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    h = constrain(h, "batch", None, "model")
    return jax.shard_map(lambda hl, wl: hl @ wl,
                         in_specs=(P(None, None, "model"), P("model", None)),
                         out_specs=P(), check_vma=False)(h, p["wo"])


def run(cell, rope_scaling=None, drop_exchange=False):
    """One whole run of the cell through the harness; the program at the
    registry's RoPE scaling, or at ``rope_scaling``; with
    ``drop_exchange``, without the MLP's all-reduce between devices."""
    import jax

    from benchlib import harness
    from repro.models import layers, model

    kw = {} if rope_scaling is None else {"rope_scaling": rope_scaling}
    harness.program_config = lambda config: tiny_program_config(config, **kw)
    harness.enable_compile_cache = lambda: "off"
    model.apply_mlp = mlp_without_exchange if drop_exchange \
        else layers.apply_mlp
    t0 = time.monotonic()
    try:
        line = harness.run_cell(cell, SEED, 2.0, False, t_start=t0,
                                devices=jax.devices(), log=lambda *_: None)
    finally:
        model.apply_mlp = layers.apply_mlp
    return line, t0


def build_spans(since: float) -> list[dict]:
    from repro.core import tracing

    return [{k: v for k, v in s.attrs.items()}
            for s in tracing.spans(prefix="chief.build", since=since)]


def main() -> None:
    import jax
    import numpy as np

    from benchlib import check, traffic as gen
    from benchlib.harness import OPT, reference_run
    from benchlib.spec import load_json, load_module, reference_module

    assert len(jax.devices()) == 4, jax.devices()
    cell = tiny_cell()
    limits = load_json(BENCH / "limits" / f"{CELL}.json")
    out = {}
    sound, t0 = run(cell)
    out["sound"] = sound
    out["builds"] = build_spans(t0)
    out["unscaled"], _ = run(cell, rope_scaling=1.0)
    out["dropped_exchange"], _ = run(cell, drop_exchange=True)

    n = limits["compared_steps"]
    tokens = gen.make_tokens(cell.traffic, cell.config["vocab_size"], 11)
    ref = reference_run(cell, tokens, n)
    fp8 = reference_run(cell, tokens, n, matmul="fp8")
    out["control"] = {k: v for k, (v, _) in check.numbers(fp8, ref).items()}

    # the sharded reference against the plain one, at factor 1 (the plain
    # one has no scaling)
    unscaled = dataclasses.replace(cell, config={
        k: v for k, v in cell.config.items() if k != "rope_scaling"})
    sharded = reference_module(unscaled.config)
    plain = load_module(BENCH / "configs" / "dense_decoder_ref.py",
                        "dense_decoder_ref")
    batches = [gen.batch_at(tokens, cell.traffic, s) for s in range(n)]
    out["mesh_size"] = sharded.mesh_for(cell.config).devices.size
    out["sharded_ref"] = sharded.train_steps(unscaled.config, batches, OPT)
    out["plain_ref"] = plain.train_steps(unscaled.config, batches, OPT)
    params = sharded.init_params(cell.config, sharded.mesh_for(cell.config))
    out["split_leaves"] = sorted(
        k for k, a in params.items() if not a.sharding.is_fully_replicated)
    # the same draw as the plain reference's, compiled as the program's is
    drawn = jax.jit(lambda: plain.init_params(cell.config))()
    out["init_equal"] = all(np.array_equal(np.asarray(params[k]),
                                           np.asarray(v))
                            for k, v in drawn.items())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
