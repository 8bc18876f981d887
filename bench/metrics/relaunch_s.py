"""relaunch_s: from the kill (chaos_injected) to the relaunched chief
program's entry: teardown, retry backoff, allocation and launch."""


def read(run):
    entry = run.entries.get(2)
    if not run.chaos or entry is None:
        return None
    return entry - run.chaos[0]
