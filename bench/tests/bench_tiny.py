"""Helpers for the benchmark's CPU tests: a tiny cell of each traffic mix,
run through the whole harness on the CPU."""
import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256)


def tiny_cell(name: str):
    from benchlib.spec import load_cell

    cell = load_cell(name)
    traffic = dict(cell.traffic, batch=4, seq_len=32)
    if traffic.get("ckpt_every"):
        traffic.update(ckpt_every=6, kill_after_save=3)
    return dataclasses.replace(cell, config=dict(cell.config, **TINY),
                               traffic=traffic)


def tiny_program_config(config: dict):
    """The program's configuration at the tiny sizes, computing in float32
    so that a sound run agrees with the reference to round-off."""
    from repro.configs import get_config

    return get_config(config["program_config"]).replace(
        num_layers=TINY["num_hidden_layers"], d_model=TINY["hidden_size"],
        num_heads=TINY["num_attention_heads"],
        num_kv_heads=TINY["num_key_value_heads"], head_dim=TINY["head_dim"],
        d_ff=TINY["intermediate_size"], vocab_size=TINY["vocab_size"],
        dtype="float32", compute_param_dtype="float32")


@pytest.fixture
def run_tiny(monkeypatch, tmp_path):
    """Runs a tiny cell through run_cell on the CPU, past the chip check."""
    import time

    import jax

    from benchlib import harness

    monkeypatch.setattr(harness, "program_config", tiny_program_config)
    # a persistent cache shared with other processes' CPU compiles only adds
    # noise here; the chip's runs keep theirs
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")

    def run(name: str, fault=None, seconds: float = 2.0, seed: int = 7):
        return harness.run_cell(tiny_cell(name), seed, seconds, False,
                                t_start=time.monotonic(),
                                devices=jax.devices(), fault=fault,
                                log=lambda *_: None)

    return run
