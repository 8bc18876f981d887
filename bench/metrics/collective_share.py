"""collective_share: the share of device 0's traced window in which a
collective runs and no compute op overlaps it, in percent: the self time
(``benchlib.trace.self_times``: the time in which an op is the innermost
one running) of every op whose HLO name is a collective's, in its plain,
``-start`` or ``-done`` form, over the window. Where XLA overlaps a
collective with compute, the compute op is the innermost one, so only the
exposed part counts."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def read(run):
    if run.trace is None or not run.trace.get("ops_s"):
        return None
    spent = sum(s for name, s in run.trace["ops_s"].items()
                if is_collective(name))
    return 100.0 * spent / run.trace["window_s"]
