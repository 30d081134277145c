"""Seconds of XLA backend compiles during set-up, from ``jax.monitoring``
(a run that finds every program in the persistent cache reads near 0)."""


def read(run):
    return run.counters.get("compile_s_setup")
