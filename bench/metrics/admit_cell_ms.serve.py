"""Host milliseconds of cell preparation inside each admission: the
``repro.prepare_cell`` spans (the machines' blocks and step programs,
then the step and measure traced into structure and consts) inside each
``repro.admit`` span of the traced window, averaged over those
admissions (``repro.metrics.spans``)."""
from harness import program_trace


def read(run):
    return program_trace.child_ms_per(run, "repro.prepare_cell",
                                      "repro.admit")
