"""The one generator every training traffic file is read by.

A traffic file (``bench/traffic/<name>.json``) sets the job: batch and
sequence length, the save interval, a planned kill, the attempts allowed,
the data's shape, and the profiler's window in a traced run. From it and the
run's seed this module makes the token file the job reads, the TonY job spec
and the fault plan.

The tokens follow the program's own synthetic scheme (copied from
``repro.data.pipeline.SyntheticLMDataset`` so that the yardstick owns it):
each row tiles one of a bank of random motifs and replaces a share of its
positions with uniform noise. The same seed gives the same file; every seed
gives the same sizes, so the work a run does does not depend on it.
"""
from __future__ import annotations

import numpy as np

NO_SAVE = 10**9            # a save interval the job never reaches
NO_END = 10**6             # steps: the bench stops the job once it is measured


def tokens_per_step(traffic: dict) -> int:
    return int(traffic["batch"]) * int(traffic["seq_len"])


def make_tokens(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """A flat int32 token stream holding ``data.batches`` whole batches,
    laid out as the program's file dataset reads it: batch s is the s-th
    block of batch x (seq_len + 1) tokens."""
    data = traffic["data"]
    b, t = int(traffic["batch"]), int(traffic["seq_len"])
    n = int(data["batches"])
    rng = np.random.default_rng(seed)
    motifs = rng.integers(0, vocab, size=(data["motifs"], data["motif_len"]))
    rows = n * b + b                  # one spare batch: see batch_at
    reps = (t + 1) // data["motif_len"] + 2
    seqs = np.tile(motifs, (1, reps))[:, :t + 1][
        rng.integers(0, len(motifs), size=rows)]
    noise = rng.random((rows, t + 1)) < data["noise"]
    seqs = np.where(noise, rng.integers(0, vocab, size=(rows, t + 1)), seqs)
    return seqs.astype(np.int32).reshape(-1)


def batch_at(tokens: np.ndarray, traffic: dict, step: int) -> dict:
    """Batch ``step`` as a sequential reader of the flat stream sees it: the
    offset wraps over all but one batch, so every batch is whole."""
    b, t = int(traffic["batch"]), int(traffic["seq_len"])
    per = b * (t + 1)
    off = (step * per) % (len(tokens) - per)
    chunk = tokens[off:off + per].reshape(b, t + 1)
    return {"tokens": chunk[:, :-1], "labels": chunk[:, 1:]}


def kill_step(traffic: dict) -> int | None:
    """The step at which the chief is killed: a fixed number of steps after
    the first save, so the save has committed and the kill lands on the same
    step in every run."""
    after = traffic.get("kill_after_save")
    if after is None:
        return None
    return int(traffic["ckpt_every"]) + int(after)


def job_props(traffic: dict, chips: int, name: str) -> dict[str, str]:
    """One worker per chip; worker:0, the chief, drives every chip."""
    return {
        "tony.application.name": f"bench-{name}",
        "tony.application.max-attempts": str(traffic["max_attempts"]),
        "tony.worker.instances": str(chips),
        "tony.worker.memory": "8192",
        "tony.worker.vcores": "4",
        "tony.worker.gpus": "1",
        "tony.worker.node-label": "gpu",
    }
