"""Share of its roofline of the response kernel, z_j = A_j w_j.

Role: the device ops whose HLO instruction is named for
``feature_matvec`` (the Pallas kernel, vmapped over the machines).  Its
work per round is one pass over the configuration's whole n x d float32
matrix with its vectors (bound by bytes).  Reported in %: the least time
of that work at the published peaks, over the kernel's summed device
time.  Nothing to read (no such op in the trace) gives no value.
"""
from harness import roofline


def read(run):
    return roofline.kernel_share(run, "feature_matvec")
