"""Device milliseconds a round of the oracle kernels' B = 1 body: the ops
whose HLO ``op_name`` holds the program's ``repro.gemv`` scope
(``kernels/feature_matvec``, the VPU body of every composed kernel
called with a single right-hand side), summed over the window's trace
and divided by its rounds.  Read only from a trace of the whole window;
nothing where no op carries a ``repro.`` scope, and 0 where other scopes
do but this one is absent (a program without the B = 1 body)."""
from harness import program_trace


def read(run):
    return program_trace.scope_ms_per_round(run, "repro.gemv")
