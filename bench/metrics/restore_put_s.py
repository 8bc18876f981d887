"""restore_put_s: the relaunched chief's ``ckpt.restore.put`` span, in
seconds: ``jax.device_put`` of the restored state onto its shardings until
the bytes are on the device."""
from benchlib.spans import of_attempt, run_spans


def read(run):
    spans = run_spans(run)
    if spans is None:
        return None
    span = of_attempt(run, spans, "ckpt.restore.put", 2)
    return None if span is None else span.duration
