"""Host milliseconds of the instance build inside each admission: the
``repro.instance_build`` spans (data, smoothness bound, per-block
constants, f*) inside each ``repro.admit`` span of the traced window,
averaged over those admissions (``repro.metrics.spans``)."""
from harness import program_trace


def read(run):
    return program_trace.child_ms_per(run, "repro.instance_build",
                                      "repro.admit")
