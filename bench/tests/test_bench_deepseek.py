"""The configuration deepseek-coder-33b and its four-chip cell on the CPU:
the file agrees with the program's registry, the collective reader counts by
hand, and a tiny copy of the cell runs through the whole harness on four
devices (``deepseek_tiny.py``, in a process of its own)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import BENCH, ROOT
from benchlib.record import RunRecord
from benchlib.spec import load_cell, load_json, metric_reader

CELL = "deepseek-coder-33b.train-4chip"
CONFIG = load_json(BENCH / "configs" / "deepseek-coder-33b.json")
LIMITS = load_json(BENCH / "limits" / f"{CELL}.json")["limits"]


def test_file_agrees_with_the_registry():
    """``harness.program_config`` compares the widths; this ties the rest:
    the published scaling, the source, the one cut, and what the program
    has no key for."""
    from benchlib.harness import program_config
    from repro.configs import get_config

    cfg = get_config(CONFIG["program_config"])
    assert CONFIG["rope_scaling"] == {"type": "linear", "factor": 4.0}
    assert cfg.rope_scaling == CONFIG["rope_scaling"]["factor"]
    assert cfg.source == CONFIG["source"]
    assert CONFIG["reduced"] == {"num_hidden_layers": [62, 4]}
    assert cfg.num_layers == 62
    assert program_config(CONFIG).num_layers == CONFIG["num_hidden_layers"]
    assert (cfg.mlp_variant, CONFIG["hidden_act"]) == ("swiglu", "silu")
    assert cfg.pos_embedding == "rope" and not CONFIG["attention_bias"]
    assert cfg.resolved_head_dim == CONFIG["head_dim"] == (
        CONFIG["hidden_size"] // CONFIG["num_attention_heads"])
    # every split of the four-chip layout is even
    for size in (cfg.num_heads, cfg.num_kv_heads, cfg.d_ff, cfg.vocab_size):
        assert size % 4 == 0


def test_collective_share_against_a_hand_count():
    cell = load_cell(CELL)

    def record(trace):
        return RunRecord(cell=cell, seconds=1.0, setup_s=0.0, t_submit=0.0,
                         t_open=0.0, t_close=1.0, steps=[], entries={},
                         chaos=[], commits=[], compiles=[],
                         flops_per_step=1.0, tokens_per_step=1,
                         peak_flops=1.0, chips=4, trace=trace)

    read = metric_reader("collective_share")
    ops = {"%all-reduce.8": 0.10, "%all-reduce-start.3": 0.02,
           "all-reduce-done.3": 0.03, "%all-gather.1": 0.04,
           "%reduce-scatter.2": 0.05, "%collective-permute-done": 0.06,
           "%all-to-all.7": 0.10,
           # compute, whatever else its name holds
           "%fusion.280": 1.0, "%convolution_all-reduce_fusion": 0.2,
           "%copy.4": 0.1}
    assert read(record({"window_s": 2.0, "ops_s": ops})) == pytest.approx(
        100.0 * 0.40 / 2.0)
    assert read(record({"window_s": 2.0, "ops_s": {"%fusion.1": 1.0}})) == 0
    assert read(record(None)) is None


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, str(BENCH / "tests" /
                                            "deepseek_tiny.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tiny_cell_on_four_devices_is_correct(four):
    line = four["sound"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == set(LIMITS)
    assert set(line["metrics"]) == {"train_tokens_per_s", "step_ms_p90",
                                    "job_start_s", "setup_s"}
    assert line["device"]["count"] == 4
    [build] = four["builds"]
    assert build["chips"] == 4 and build["mesh"] == [1, 4]


@pytest.mark.parametrize("variant", ["control", "unscaled"])
def test_control_and_unscaled_program_fail_a_limit(four, variant):
    """The reference in fp8, and the program without the published RoPE
    scaling against the scaled reference: each fails a limit."""
    if variant == "control":
        numbers = four["control"]
    else:
        assert not four["unscaled"]["correct"]
        numbers = {k: c["value"]
                   for k, c in four["unscaled"]["checks"].items()}
    assert [k for k, v in numbers.items() if v > LIMITS[k]], numbers


def test_dropped_exchange_fails_a_limit(four):
    """The program with its MLP's all-reduce between the four devices left
    out (each device goes on with its own partial sum): the job runs, and
    the check fails it."""
    line = four["dropped_exchange"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert not line["correct"]
    assert [k for k, c in line["checks"].items() if c["value"] > LIMITS[k]]


def test_sharded_reference_equals_the_plain_one(four):
    """At factor 1, over four devices, against ``dense_decoder_ref``: the
    same initial weights, and the same losses and norms to float32
    round-off (the split products add in another order)."""
    assert four["mesh_size"] == 4
    assert four["split_leaves"] == sorted(
        ["decoder/0/0/mixer/" + w for w in ("wq", "wk", "wv", "wo")]
        + ["decoder/0/0/mlp/" + w for w in ("wi_gate", "wi_up", "wo")]
        + ["embed", "lm_head"])
    assert four["init_equal"]
    sharded, plain = four["sharded_ref"], four["plain_ref"]
    np.testing.assert_allclose(sharded["losses"], plain["losses"], rtol=1e-5)
    for key in ("grad0_norms", "change_norms"):
        assert sorted(sharded[key]) == sorted(plain[key])
        for leaf, v in plain[key].items():
            assert sharded[key][leaf] == pytest.approx(v, rel=1e-4), leaf


def test_sharded_reference_rope_divides_the_positions():
    """The reference's scaled rotation against the program's, at factor 4:
    two independent codings of the positions over the factor."""
    import jax
    import jax.numpy as jnp

    from benchlib.spec import reference_module
    from repro.models.layers import apply_rope, rope_angles

    ref = reference_module(CONFIG)
    assert ref.rope_factor(CONFIG) == 4.0
    assert ref.rope_factor({}) == 1.0
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 2, 16), jnp.float32)
    sin, cos = rope_angles(jnp.arange(64), 16, 1e5, 4.0)
    want = apply_rope(x[None], sin, cos)[0]
    np.testing.assert_allclose(ref._rope(x, 1e5, 4.0), want, atol=1e-6)
    assert not np.allclose(ref._rope(x, 1e5, 1.0), want, atol=1e-3)
