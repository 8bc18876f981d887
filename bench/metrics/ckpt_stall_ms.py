"""ckpt_stall_ms: mean interval of the steps followed by a save minus the
median interval of the steps not followed by one, in milliseconds, over the
window's step intervals. A save follows step s when (s + 1) is a multiple
of the traffic's ckpt_every; its snapshot lands in the next interval."""
import numpy as np


def read(run):
    every = run.cell.traffic.get("ckpt_every")
    if not every:
        return None
    gaps = run.intervals()
    saved = [g for step, g in gaps if step % every == 0]
    plain = [g for step, g in gaps if step % every != 0]
    if not saved or not plain:
        return None
    return (float(np.mean(saved)) - float(np.median(plain))) * 1e3
