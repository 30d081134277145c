"""Host seconds of the sharded instance build in set-up: A drawn
column-sharded over the mesh, L from A^T A on the devices, and the
program's own reference solve for f*, timed around
``ExecutionPlan.bundle`` from outside."""


def read(run):
    return run.phases.get("instance_build")
