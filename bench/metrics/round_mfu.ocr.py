"""The whole round's share of the cell's chips' peak, in %.

Rounds per second over the traced window, times the least time of one
read of the whole n x d float32 matrix with the R^n and R^d vectors at
one chip's published peaks, over the cell's chips: the four chips
together can read the matrix no faster than in a quarter of that time.
Bound by bytes, and a bound on any gain whatever the kernels.
"""
from harness import roofline


def read(run):
    rounds, window = run.counters.get("rounds"), run.counters.get("window_s")
    if not rounds or not window or run.peaks is None:
        return None
    p = run.cell.config["instance_params"]
    ops, nbytes = roofline.dense_pass(p["n"], p["d"])
    return 100.0 * roofline.least_seconds(ops, nbytes, run.peaks) \
        * rounds / window / run.cell.chips
