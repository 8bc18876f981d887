"""host_gap_ms: the host's own time in a step, in milliseconds: the median,
over the window's ``train.step`` spans that hold no ``ckpt.save``, of the
span's duration minus that of its ``train.loss_wait`` (the wait for the
step's loss, during which the device runs). What is left is the chaos hook,
the batch, its transfer, the dispatch and ``on_step``."""
import statistics

from benchlib.spans import children, in_window, run_spans


def read(run):
    spans = run_spans(run)
    if spans is None:
        return None
    kids = children(spans)
    gaps = []
    for step in in_window(run, spans, "train.step"):
        inner = kids.get(step.span_id, {})
        if "ckpt.save" in inner or "train.loss_wait" not in inner:
            continue
        gaps.append(step.duration - inner["train.loss_wait"][0].duration)
    return statistics.median(gaps) * 1e3 if gaps else None
