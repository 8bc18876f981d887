"""job_compile_s: seconds of backend compiles JAX reports between submit and
attempt 1's first step. Loads from the persistent cache report their
(short) durations too."""


def read(run):
    first = run.first_step(1)
    return sum(secs for t, secs, _, _ in run.compiles
               if run.t_submit <= t <= first)
