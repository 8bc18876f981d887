"""step_mfu: the training step's share of the chips' bf16 peak, in percent:
model FLOPs of one step (6 x matmul parameters + causal attention per token,
recomputation not counted; see benchlib/flops.py) over the median interval
between consecutive completed steps in the window, the chips and the peak
of the device kind. Stalls between steps (a save's snapshot, a resume) are
other layers' metrics; the median leaves them out."""
import math

import numpy as np


def read(run):
    gaps = [s for _, s in run.intervals()]
    if math.isnan(run.peak_flops) or not gaps:
        return None
    per_s = run.flops_per_step / float(np.median(gaps))
    return 100.0 * per_s / (run.chips * run.peak_flops)
