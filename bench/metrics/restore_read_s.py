"""restore_read_s: the relaunched chief's ``ckpt.restore.read`` span, in
seconds: the checkpoint's npz read, its key and shape checks and the
unflatten into the state's structure, on the host."""
from benchlib.spans import of_attempt, run_spans


def read(run):
    spans = run_spans(run)
    if spans is None:
        return None
    span = of_attempt(run, spans, "ckpt.restore.read", 2)
    return None if span is None else span.duration
