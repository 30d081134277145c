"""The devices' idle share of the traced window, in %: 1 - (union of
each chip's op intervals / window), averaged over the cell's chips."""
from harness import trace


def read(run):
    return trace.idle_share(run)
