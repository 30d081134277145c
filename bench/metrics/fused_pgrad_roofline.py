"""Share of its roofline of the partial-gradient kernel,
g_j = (A_j^T l'(z) / n + lam w_j) masked.

Role: the device ops whose HLO instruction is named for ``fused_pgrad``
(the Pallas kernel with its epilogue, vmapped over the machines).  Its
work per round is one pass over the configuration's whole n x d float32
matrix with its vectors (bound by bytes; the epilogue's d-vector work is
inside the vectors).  Reported in %: the least time of that work at the
published peaks, over the kernel's summed device time.
"""
from harness import roofline


def read(run):
    return roofline.kernel_share(run, "fused_pgrad")
