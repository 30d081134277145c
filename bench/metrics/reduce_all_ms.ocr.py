"""Device milliseconds a round of the per-round ReduceAll on the mesh, a
chip: the ops whose HLO ``op_name`` holds the comm scope of a
``reduce_all`` record (``comm[...;k=reduce_all;...]``, set where the
program prices the message), summed over the window's trace and its
chips, over the rounds and the cell's chips.  The gap measure's own
psum sits under ``repro.gap`` and is not read here.  Nothing where the
trace does not cover the window or no op carries a ``repro.`` scope."""
from harness import program_trace


def read(run):
    ms = program_trace.scope_ms_per_round(run, "k=reduce_all")
    return None if ms is None else ms / run.cell.chips
