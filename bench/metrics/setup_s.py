"""setup_s: process start to ready-to-submit (imports, backend, compile
cache, and the warm-up that builds this cell's init and step programs)."""


def read(run):
    return run.setup_s
