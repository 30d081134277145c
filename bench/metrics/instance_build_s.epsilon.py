"""Host seconds of the instance build in set-up: data generation, the
spectral norm, the per-block constants and the program's own reference
solve for f*, timed around ``ExecutionPlan.bundle`` from outside."""


def read(run):
    return run.phases.get("instance_build")
