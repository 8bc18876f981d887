"""restore_to_step_s: from the relaunched chief program's entry to its first
completed step: checkpoint read, device_put, compile-cache loads, the step."""


def read(run):
    entry, first = run.entries.get(2), run.first_step(2)
    if entry is None or first is None:
        return None
    return first - entry
