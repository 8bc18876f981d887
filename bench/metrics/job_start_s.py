"""job_start_s: submit to the job's first completed step (allocation, AM,
executor launch, rendezvous, mesh, the job's compile-cache loads, init on
the device and the first step)."""


def read(run):
    return run.first_step(1) - run.t_submit
