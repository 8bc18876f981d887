"""resume_s: from the kill (the chaos_injected event) to the relaunched
attempt's first completed step."""


def read(run):
    second = run.first_step(2)
    if not run.chaos or second is None:
        return None
    return second - run.chaos[0]
