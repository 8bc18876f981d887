"""The readers of the program's spans, on the CPU at a tiny size: each
reads a finite, non-negative number from a whole run of its cells."""
import dataclasses
import math
import time

import jax
import pytest

from bench_tiny import tiny_cell, tiny_program_config

SPAN_METRICS = {"host_gap_ms", "data_wait_ms", "ckpt_snapshot_ms",
                "restore_read_s", "restore_put_s"}


@pytest.mark.parametrize("name", ["qwen3-1.7b.train",
                                  "qwen3-1.7b.train-ckpt-kill"])
def test_span_metrics_read_a_whole_run(name, monkeypatch, tmp_path):
    from benchlib import harness

    monkeypatch.setattr(harness, "program_config", tiny_program_config)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    cell = tiny_cell(name)
    # the cell's span readers in place of its end-to-end metrics, so that
    # an untraced run reports them
    readers = tuple(m for m in cell.per_layer if m.name in SPAN_METRICS)
    cell = dataclasses.replace(cell, end_to_end=readers)
    line = harness.run_cell(cell, 2**31 + 7, 3.0, False,
                            t_start=time.monotonic(), devices=jax.devices(),
                            log=lambda *_: None)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {m.name for m in readers}
    if name.endswith("ckpt-kill"):
        assert set(line["metrics"]) == SPAN_METRICS
    for metric, v in line["metrics"].items():
        assert math.isfinite(v["value"]) and v["value"] >= 0, metric
