"""data_wait_ms: mean ``train.next_batch`` span in the window, in
milliseconds: how long the chief waited for the data pipeline's next batch
(``PrefetchingLoader`` builds batches ahead on its own thread)."""
import statistics

from benchlib.spans import in_window, run_spans


def read(run):
    spans = run_spans(run)
    if spans is None:
        return None
    waits = [s.duration for s in in_window(run, spans, "train.next_batch")]
    return statistics.mean(waits) * 1e3 if waits else None
