"""The harness on the CPU at a tiny size: a sound run is correct, and a run
whose step is broken underneath is not."""
import math

import pytest

from bench_tiny import run_tiny  # noqa: F401  (fixture)


def test_sound_steady_run_is_correct(run_tiny):
    line = run_tiny("qwen3-1.7b.train")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "step_ms_p90",
                                    "job_start_s", "setup_s"}
    assert list(line)[-1] == "checks"


def test_sound_kill_run_resumes_bit_equal(run_tiny):
    line = run_tiny("qwen3-1.7b.train-ckpt-kill", seconds=3.0)
    assert line["correct"], line["checks"]
    assert line["checks"]["restore_gap"]["value"] == 0.0
    assert math.isfinite(line["metrics"]["resume_s"]["value"])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(run_tiny, fault):
    line = run_tiny("qwen3-1.7b.train", fault=fault)
    assert not line["correct"]
    over = [n for n, c in line["checks"].items() if c["value"] > c["limit"]]
    assert over, line["checks"]
