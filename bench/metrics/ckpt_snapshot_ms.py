"""ckpt_snapshot_ms: the training thread's time in a save, in milliseconds:
mean over the window's ``ckpt.save`` spans of their ``ckpt.snapshot`` (the
device-to-host copy of the state) plus ``ckpt.handoff`` (the wait for the
writer's slot and the hand-off). The write itself runs on the writer
thread, which carries no span."""
import statistics

from benchlib.spans import children, in_window, run_spans


def read(run):
    spans = run_spans(run)
    if spans is None:
        return None
    kids = children(spans)
    saves = []
    for save in in_window(run, spans, "ckpt.save"):
        inner = kids.get(save.span_id, {})
        saves.append(sum(s.duration for name in ("ckpt.snapshot",
                                                 "ckpt.handoff")
                         for s in inner.get(name, ())))
    return statistics.mean(saves) * 1e3 if saves else None
