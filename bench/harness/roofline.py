"""The work a kernel or a round needs, from the configuration's shapes,
and the least time the chip could take for it.

The work is what the mathematics requires, not what an implementation
moves: a dense product with an n x d float32 matrix reads the unpadded
matrix once and its vectors once, and does 2 n d operations.  Padding,
re-reads and wider right-hand sides are the implementation's cost and
show as a lower share.
"""
from __future__ import annotations

from typing import Optional, Tuple


def dense_pass(n: int, d: int, itemsize: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of one product of an n x d matrix with a
    vector, or of its transpose with one: the matrix, the vector in and
    the vector out."""
    return 2.0 * n * d, float(itemsize) * (n * d + n + d)


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The larger of operations over the peak rate (bfloat16, the
    fastest the chip multiplies) and bytes over the memory bandwidth."""
    return max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def bound_by(ops: float, nbytes: float, peaks: dict) -> str:
    """Which of the two limits ``least_seconds`` took."""
    return ("bytes" if nbytes / peaks["hbm_bytes_per_s"]
            >= ops / peaks["flops_bf16"] else "operations")


def kernel_share(run, kernel: str) -> Optional[float]:
    """In %, the share of its roofline of the kernel whose device ops
    carry ``kernel`` in their label: one dense pass of the configuration's
    n x d matrix per round of the window, at the least time the peaks
    allow, over the summed device time of those ops.  None where the
    trace does not span the whole window (its rounds are then not the
    window's) or holds no such op."""
    from . import trace
    if (run.device_trace is None or not run.counters.get("rounds")
            or run.counters.get("traced_from_s", 0.0) > 0.0):
        return None
    seconds = trace.op_seconds(run.device_trace,
                               lambda name: kernel in trace.label(name))
    if seconds <= 0:
        return None
    p = run.cell.config["instance_params"]
    ops, nbytes = dense_pass(p["n"], p["d"])
    return 100.0 * least_seconds(ops, nbytes, run.peaks) \
        * run.counters["rounds"] / seconds
