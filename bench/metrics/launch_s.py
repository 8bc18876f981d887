"""launch_s: submit to the chief program's entry in attempt 1, taken by the
bench's wrapper around the ML program (client, RM, AM, executor launch,
registration and cluster spec)."""


def read(run):
    entry = run.entries.get(1)
    return None if entry is None else entry - run.t_submit
