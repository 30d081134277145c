"""The reduction from a profiler trace to the benchmark's numbers.

A ``--trace 1`` run records the window with ``jax.profiler.trace``
(Python tracer off; host annotations on).  The ``.xplane.pb`` it writes
holds, per TPU, a plane ``/device:TPU:<i>`` whose ``XLA Ops`` line has
one event per operation that ran on the device; an event is named by
its HLO text (``%fusion.3 = f32[...] fusion(...), ...``), and a Pallas
kernel's by its custom call, whose text carries the kernel's name.  The
host plane holds the harness's own spans (``bench.*``,
``jax.profiler.TraceAnnotation``) on the same clock.

From those: the device's busy time (the union of its op intervals inside
the window, averaged over the chips), the seconds of the ops a predicate
selects, the ops that took most time, and the longest idle gaps, each
named by the harness span that covered it.  A trace whose device ops
start late in its window, or end early, is not read at all
(``covers``).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import shutil
from typing import Callable, Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."

Interval = Tuple[str, float, float]           # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]            # device plane -> op events
    spans: List[Interval]                     # the harness's host spans

    def window(self) -> Tuple[float, float]:
        """The traced window: the ``bench.window`` span, else the extent
        of everything recorded."""
        for name, s, e in self.spans:
            if name == SPAN_PREFIX + "window":
                return s, e
        every = [iv for evs in self.ops.values() for iv in evs] + self.spans
        return min(s for _, s, _ in every), max(e for _, _, e in every)


def read(trace_dir) -> Trace:
    """Load the newest ``.xplane.pb`` under ``trace_dir``."""
    import pathlib
    from jax.profiler import ProfileData
    paths = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(paths[-1])))


def leaves(events: List[Interval]) -> List[Interval]:
    """Drop the container ops: a ``while`` loop's event spans every op of
    its body on the same line.  An event counts as a container when the
    events that start and end inside it fill more than half of it (an op
    that merely overlaps a short neighbour stays)."""
    ordered = sorted(events, key=lambda iv: (iv[1], -iv[2]))
    out = []
    for i, (name, s, e) in enumerate(ordered):
        inside, j = 0.0, i + 1
        while j < len(ordered) and ordered[j][1] < e:
            if ordered[j][2] <= e:
                inside += ordered[j][2] - ordered[j][1]
            j += 1
        if inside <= 0.5 * (e - s):
            out.append((name, s, e))
    return out


def from_profile(profile) -> Trace:
    ops: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = leaves([(ev.name, ev.start_ns,
                                               ev.end_ns)
                                              for ev in line.events])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns, ev.end_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]))


def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], sorted."""
    merged: List[Tuple[float, float]] = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the traced chips."""
    lo, hi = trace.window()
    if not trace.ops:
        return 0.0
    per = [sum(e - s for s, e in union(evs, lo, hi))
           for evs in trace.ops.values()]
    return sum(per) / len(per) / 1e9


_HLO_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_KERNEL = re.compile(r'kernel_name\s*=\s*\\?"?([\w\-]+)')


def label(name: str) -> str:
    """A short, stable label for an op event: a Pallas kernel's name where
    its HLO text carries one, else the HLO instruction name without its
    numeric suffix."""
    m = _KERNEL.search(name)
    if m:
        return m.group(1)
    m = _HLO_NAME.match(name)
    return m.group(1) if m else name.split(" ", 1)[0][:64]


def op_seconds(trace: Trace, select: Callable[[str], bool]) -> float:
    """Device seconds of the ops ``select`` picks (summed over chips)."""
    lo, hi = trace.window()
    return sum(min(e, hi) - max(s, lo)
               for evs in trace.ops.values() for name, s, e in evs
               if select(name) and min(e, hi) > max(s, lo)) / 1e9


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    lo, hi = trace.window()
    total: Dict[str, float] = {}
    for evs in trace.ops.values():
        for name, s, e in evs:
            if min(e, hi) > max(s, lo):
                key = label(name)
                total[key] = total.get(key, 0.0) + (min(e, hi) - max(s, lo))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest stretches with no op on the first chip, each
    named by the innermost harness span covering its middle."""
    lo, hi = trace.window()
    if not trace.ops:
        return []
    busy = union(next(iter(trace.ops.values())), lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        covering = [sp for sp in trace.spans if sp[1] <= mid <= sp[2]
                    and sp[0] != SPAN_PREFIX + "window"]
        name = (min(covering, key=lambda sp: sp[2] - sp[1])[0]
                if covering else "no harness span")
        out.append([name, (e - s) / 1e9])
    return out


# The trace covers the window where its first device op starts, and its
# last ends, within this share of the window of the window's ends.  The
# TPU profiler keeps a fixed number of trace buffers; a window with more
# device events than they hold loses its oldest ones, and the trace then
# starts late.
COVERAGE = 0.1


def coverage(t: Trace) -> Tuple[float, float]:
    """(seconds from the window's opening to its first device op, seconds
    from the last device op's end to the window's close), over the chips'
    worst."""
    lo, hi = t.window()
    evs = [iv for ops in t.ops.values() for iv in ops
           if min(iv[2], hi) > max(iv[1], lo)]
    if not evs:
        return (hi - lo) / 1e9, (hi - lo) / 1e9
    return ((max(min(s for _, s, _ in evs), lo) - lo) / 1e9,
            (hi - min(max(e for _, _, e in evs), hi)) / 1e9)


def covers(t: Trace) -> bool:
    lo, hi = t.window()
    return max(coverage(t)) <= COVERAGE * (hi - lo) / 1e9


class _Recorder:
    """Starts and stops the profiler for ``window``."""

    def __init__(self, run, start_after: float):
        self.run, self.start_after = run, start_after
        self.span, self.started = None, None

    def mark(self, elapsed: float) -> None:
        if self.run.trace and self.span is None and \
                elapsed >= self.start_after:
            import jax
            shutil.rmtree(self.run.work_dir, ignore_errors=True)
            self.run.work_dir.mkdir(parents=True, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.run.work_dir),
                                     profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation(SPAN_PREFIX + "window")
            self.span.__enter__()
            self.started = elapsed

    def stop(self) -> None:
        if self.span is None:
            return
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        t = read(self.run.work_dir)
        lo, hi = t.window()
        first, last = coverage(t)
        counts = collections.Counter(label(name) for evs in t.ops.values()
                                     for name, _, _ in evs)
        self.run.note(f"trace: from {self.started:.3f} s into the window, "
                      f"{(hi - lo) / 1e9:.3f} s, {sum(counts.values())} "
                      f"device ops, the first {first:.3f} s after it opens, "
                      f"the last {last:.3f} s before it closes; most "
                      f"frequent {counts.most_common(3)}")
        if covers(t):
            self.run.device_trace = t
            self.run.counters["traced_from_s"] = self.started
        else:
            self.run.note("trace: the device ops do not cover the traced "
                          "window (events dropped, or none ran); no "
                          "per-layer metric is read from it")


@contextlib.contextmanager
def window(run, tail_s: Optional[float] = None):
    """The measured window.  In a ``--trace 1`` run the profiler records
    it inside a ``bench.window`` span and leaves the reduction on
    ``run.device_trace`` (None where the trace does not cover its
    window).  With ``tail_s`` it records only the window's last
    ``tail_s`` seconds and what follows until the block ends: the driver
    calls the yielded ``mark(seconds since the window opened)`` between
    units of work, and the first call at or past ``run.seconds - tail_s``
    starts the profiler."""
    rec = _Recorder(run, 0.0 if tail_s is None
                    else max(0.0, run.seconds - tail_s))
    rec.mark(0.0)
    try:
        yield rec.mark
    finally:
        rec.stop()


def busy_and_window(run) -> Optional[Tuple[float, float]]:
    """(device busy seconds, traced window seconds), or None where the
    run kept no trace that covers its window."""
    t: Optional[Trace] = run.device_trace
    if t is None:
        return None
    lo, hi = t.window()
    return busy_seconds(t), (hi - lo) / 1e9


def idle_share(run) -> Optional[float]:
    """The device's idle share of the traced window, in %."""
    got = busy_and_window(run)
    if got is None or got[1] <= 0:
        return None
    return 100.0 * (1.0 - got[0] / got[1])


def breakdown(run) -> dict:
    t: Trace = run.device_trace
    return {"device_ops": top_ops(t), "idle_gaps": idle_gaps(t)}
