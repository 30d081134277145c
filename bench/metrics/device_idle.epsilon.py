"""The device's idle share of the traced window, in %: 1 - (union of
the device's op intervals / window), averaged over the chips."""
from harness import trace


def read(run):
    return trace.idle_share(run)
