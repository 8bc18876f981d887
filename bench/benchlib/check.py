"""How ``correct`` is decided: the job's first steps against the reference.

The reference (the configuration's plain float32 model, ``configs/<ref>.py``)
trains from the same seeded weights on the same batches the job read, once
the job has ended and its state is freed. The numbers compared, each with its
limit from ``bench/limits/<cell>.json``:

* ``grad_gap``: step 0's gradient as the optimizer got it, worked out from
  the job's first moment after step 0 (m = (1 - b1) x clip scale x g) and
  the step's reported global norm; per leaf the gap between the job's norm
  and the reference's, over the larger of the reference's norm of that leaf
  and of the median leaf; the worst leaf;
* ``grad_gap_median``: that gap at the median leaf, which every matrix
  of the model moves and no single leaf sets;
* ``change_gap``: the same measure for the weights' change over the
  compared steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone under
  AdamW);
* ``restore_gap`` (cells with a planned kill): the largest gap between a
  step's loss before the kill and the same step's loss recomputed after the
  resume. Both come from the same compiled step on the same restored bits
  and the same batch, so the limit is 0.

The losses are printed beside them (``diagnostics``) and not compared: the
fp8 control does not separate from bfloat16 on them, and after step 0 they
carry AdamW's first update, which moves every element by the learning rate
in the sign of its gradient, so elements whose gradient is rounding noise
move either way on the two sides.
"""
from __future__ import annotations

import numpy as np

DEAD_LEAF = 1e-3


def relative_gap(prog: float, ref: float) -> float:
    return abs(prog - ref) / abs(ref)


def worst_leaf_gap(prog: dict[str, float], ref: dict[str, float],
                   leaves: list[str]) -> tuple[float, str]:
    floor = float(np.median([ref[k] for k in leaves]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def median_leaf_gap(prog: dict[str, float], ref: dict[str, float],
                    leaves: list[str]) -> tuple[float, str]:
    """The same per-leaf gap as ``worst_leaf_gap``, at the median leaf."""
    floor = float(np.median([ref[k] for k in leaves]))
    gaps = sorted((abs(prog[k] - ref[k]) / max(ref[k], floor), k)
                  for k in leaves)
    return gaps[len(gaps) // 2]


def program_grad0(moment0: dict[str, float], grad_norm0: float,
                  b1: float, clip: float) -> dict[str, float]:
    """Undo AdamW's first-moment decay and global-norm clip."""
    scale = min(1.0, clip / (grad_norm0 + 1e-9))
    return {k: v / (1.0 - b1) / scale for k, v in moment0.items()}


def numbers(job: dict, ref: dict) -> dict[str, tuple[float, str]]:
    """job and ref: ``grad0_norms`` (step 0's gradient before clipping) and
    ``change_norms`` (the weights' change over the compared steps), per
    leaf, by the program's leaf names. Returns name -> (value, where it was
    taken)."""
    out = {}
    leaves = sorted(ref["grad0_norms"])
    if sorted(job["grad0_norms"]) != leaves:
        raise ValueError(f"leaves differ: job {sorted(job['grad0_norms'])} "
                         f"vs reference {leaves}")
    out["grad_gap"] = worst_leaf_gap(job["grad0_norms"], ref["grad0_norms"],
                                     leaves)
    out["grad_gap_median"] = median_leaf_gap(job["grad0_norms"],
                                             ref["grad0_norms"], leaves)
    median = float(np.median([ref["grad0_norms"][k] for k in leaves]))
    moving = [k for k in leaves if ref["grad0_norms"][k] >= DEAD_LEAF * median]
    out["change_gap"] = worst_leaf_gap(job["change_norms"],
                                       ref["change_norms"], moving)
    return out


def restore_gap(steps) -> tuple[float, str] | None:
    """Largest |loss after resume - loss before the kill| over the steps
    both attempts completed; None when there is none."""
    first = {s.step: s.loss for s in steps if s.attempt == 1}
    again = {s.step: s.loss for s in steps if s.attempt == 2}
    both = sorted(set(first) & set(again))
    if not both:
        return None
    gaps = {s: abs(first[s] - again[s]) for s in both}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], f"step {worst} of {both[0]}..{both[-1]}"


def diagnostics(job: dict, ref: dict) -> dict:
    """Readings beside the compared numbers, for setting limits: each
    step's relative loss gap and each leaf's gradient and change gaps."""
    leaves = sorted(ref["grad0_norms"])
    g_floor = float(np.median([ref["grad0_norms"][k] for k in leaves]))
    c_floor = float(np.median([ref["change_norms"][k] for k in leaves]))
    return {
        "loss_gaps": [relative_gap(p, r)
                      for p, r in zip(job["losses"], ref["losses"])],
        "grad_gaps": {k: abs(job["grad0_norms"][k] - ref["grad0_norms"][k])
                      / max(ref["grad0_norms"][k], g_floor) for k in leaves},
        "change_gaps": {k: abs(job["change_norms"][k]
                               - ref["change_norms"][k])
                        / max(ref["change_norms"][k], c_floor)
                        for k in leaves},
    }
