"""train_tokens_per_s: net training progress over the window, in tokens,
divided by the window. Progress is the highest step completed (steps redone
after a resume count once), interpolated across the step in flight at each
end of the window."""


def read(run):
    return run.net_steps() * run.tokens_per_step / run.seconds
