"""Device milliseconds a round of the in-scan gap measure, a chip: the
ops whose HLO ``op_name`` holds the program's ``repro.gap`` scope (the
objective's pass over A_j and its psum), summed over the window's trace
and its chips, over the rounds and the cell's chips.  Nothing where the
trace does not cover the window or no op carries a ``repro.`` scope."""
from harness import program_trace


def read(run):
    ms = program_trace.scope_ms_per_round(run, "repro.gap")
    return None if ms is None else ms / run.cell.chips
