"""Device milliseconds a round of padding to the oracle kernels' block
grid: the ops whose HLO ``op_name`` holds the program's ``repro.pad``
scope (``kernels/feature_matvec._pad2``), summed over the window's trace
and divided by its rounds.  Read only from a trace of the whole window;
nothing where no op carries a ``repro.`` scope."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_round(run, "repro.pad")
