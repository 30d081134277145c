"""Host milliseconds inside ``CertificationService.submit`` (parse,
plan, instance build, cell trace), averaged over the window's specs and
timed by the harness around the call."""


def read(run):
    return run.counters.get("admit_ms")
