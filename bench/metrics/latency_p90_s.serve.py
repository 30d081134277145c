"""The 90th percentile (nearest rank) of certification latency, from
when a spec was due to when its envelope was released to its client,
over every spec due in the window, drained ones too (host clock).  A
stall of the host lifts it in about one run of five, so it carries no
bound."""


def read(run):
    return run.counters.get("latency_p90_s")
