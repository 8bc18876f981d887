"""step_ms_p90: 90th percentile of every interval between consecutive
completed steps of one attempt inside the window, in milliseconds. The
interval across a kill and resume is not a step interval."""
import numpy as np


def read(run):
    gaps = [s for _, s in run.intervals()]
    if not gaps:
        return None
    return float(np.percentile(gaps, 90)) * 1e3
