"""The control and the planted faults, at a size a CPU test run can hold:
each must fail at least one of the cell's limits, as they do on the chip at
the cell's own size (PERF.md gives those readings)."""
import json

import pytest

from bench_tiny import BENCH, tiny_cell
from benchlib import check, traffic as gen
from benchlib.harness import OPT, reference_run

CELL = "qwen3-1.7b.train"
LIMITS = json.loads((BENCH / "limits" / f"{CELL}.json").read_text())


@pytest.fixture(scope="module")
def runs():
    cell = tiny_cell(CELL)
    tokens = gen.make_tokens(cell.traffic, cell.config["vocab_size"], 11)
    n = LIMITS["compared_steps"]
    half = slice(0, cell.traffic["batch"] // 2)
    return {"reference": reference_run(cell, tokens, n),
            "control": reference_run(cell, tokens, n, matmul="fp8"),
            "half_batch": reference_run(cell, tokens, n, rows=half),
            "still": reference_run(cell, tokens, n, opt=dict(OPT, lr=0.0))}


def _over(numbers):
    return [k for k, (v, _) in numbers.items() if v > LIMITS["limits"][k]]


@pytest.mark.parametrize("variant", ["control", "half_batch"])
def test_control_and_fault_fail_a_limit(runs, variant):
    numbers = check.numbers(runs[variant], runs["reference"])
    assert _over(numbers), numbers


def test_unchanged_state_fails_the_norms(runs):
    """A step that returns its state unchanged: the losses of the initial
    weights, no first moment and no change."""
    ref, still = runs["reference"], runs["still"]
    assert still["change_norms"] == {k: 0.0 for k in ref["change_norms"]}
    unchanged = {"losses": still["losses"],
                 "grad0_norms": dict.fromkeys(ref["grad0_norms"], 0.0),
                 "change_norms": still["change_norms"]}
    numbers = check.numbers(unchanged, ref)
    assert numbers["grad_gap"][0] == pytest.approx(1.0)
    assert numbers["grad_gap_median"][0] == pytest.approx(1.0)
    assert numbers["change_gap"][0] == pytest.approx(1.0)
    assert {"grad_gap", "grad_gap_median", "change_gap"} <= set(_over(numbers))
