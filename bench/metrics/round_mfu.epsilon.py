"""The whole round's share of the chip's peak, in %.

Rounds per second over the traced window, times the least time of the
work one round requires at the published peaks: one read of the n x d
float32 matrix with the R^n and R^d vectors (no float32 implementation
can read A less than once per round), bound by bytes.  It stays a bound
on any gain when a later change merges, removes or adds kernels.
"""
from harness import roofline


def read(run):
    rounds, window = run.counters.get("rounds"), run.counters.get("window_s")
    if not rounds or not window or run.peaks is None:
        return None
    p = run.cell.config["instance_params"]
    ops, nbytes = roofline.dense_pass(p["n"], p["d"])
    return 100.0 * roofline.least_seconds(ops, nbytes, run.peaks) \
        * rounds / window
