"""Host milliseconds of ledger replay a released spec: the
``repro.ledger_replay`` spans of the traced window (each batch replays
its cells' trace-once schedules into fresh ledgers) over the window's
``repro.release`` spans, one per spec released (``repro.metrics.spans``)."""
from harness import program_trace


def read(run):
    return program_trace.ms_per(run, "repro.ledger_replay", "repro.release")
