"""Backend compiles (persistent-cache hits included) a spec admitted:
the ``repro.compile`` markers of the traced window, one per compile,
over its ``repro.admit`` spans.  Each marker names the innermost span
that compiled; the run notes how many each span made."""
import collections

from harness import program_trace


def read(run):
    value = program_trace.count_per(run, program_trace.COMPILE_MARKER,
                                    "repro.admit")
    if value is not None:
        t = program_trace.load(run)
        by_span = collections.Counter(
            m.args.get("span") or "outside any span"
            for m in t.named(program_trace.COMPILE_MARKER))
        run.note(f"compiles in the traced window by span: "
                 f"{dict(by_span.most_common())}")
    return value
