"""The program's spans and compile counter: where its host time goes.

``span(name, **ids)`` marks one stretch of host work at a layer
boundary (admission, instance build, cell preparation, a batch's run):

    with span("repro.admit", ticket="t000001"):
        ...

Each span enters a ``jax.profiler.TraceAnnotation`` of the same name
and ids, so under a running profiler it lands on the host plane of the
trace, on the clock of the device ops.  Ids are inherited: a span
nested in one with ``ticket=`` carries that ticket too.  Whether or not
a profiler runs, the module keeps per-name aggregates in memory (count,
total seconds, and self seconds: total minus the time of the spans
nested directly inside it), which ``snapshot()`` returns.

One ``jax.monitoring`` listener counts every backend compile (a program
found in the persistent cache counts too, with the seconds of its
lookup) under the innermost open span of the compiling thread: count,
seconds and persistent-cache hits per span.  Each compile also leaves a
zero-length ``repro.compile`` annotation carrying ``span=`` (the
innermost span, ``""`` outside any), ``fun=`` and ``seconds=``.

There is no switch: with no profiler running a span costs two clock
reads, an annotation the profiler ignores and a dict update (a few
microseconds), so spans sit at most once per solve, spec, segment or
batch, never inside a per-round loop.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
COMPILE_MARKER = "repro.compile"

# name -> [count, total seconds, self seconds]
_spans: Dict[str, List[float]] = {}
# innermost span ("" outside any) -> [count, seconds, persistent-cache hits]
_compiles: Dict[str, List[float]] = {}
_lock = threading.Lock()         # spans and compiles may close on any thread
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Frame:
    __slots__ = ("name", "ids", "child_s")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids, self.child_s = name, ids, 0.0


class span:
    """Context manager: one span of host work named ``name`` (see the
    module docstring); ``ids`` become the annotation's arguments and are
    inherited by the spans nested inside."""

    __slots__ = ("_frame", "_note", "_t0")

    def __init__(self, name: str, **ids):
        stack = _stack()
        if stack and stack[-1].ids:
            ids = {**stack[-1].ids, **ids}
        self._frame = _Frame(name, ids)

    def __enter__(self):
        _stack().append(self._frame)
        self._note = jax.profiler.TraceAnnotation(self._frame.name,
                                                  **self._frame.ids)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        took = time.perf_counter() - self._t0
        self._note.__exit__(*exc)
        stack = _stack()
        frame = stack.pop()
        if stack:
            stack[-1].child_s += took
        with _lock:
            agg = _spans.setdefault(frame.name, [0, 0.0, 0.0])
            agg[0] += 1
            agg[1] += took
            agg[2] += took - frame.child_s
        return False


def current() -> str:
    """The innermost open span of this thread, ``""`` outside any."""
    stack = _stack()
    return stack[-1].name if stack else ""


def snapshot() -> dict:
    """The aggregates so far: ``{"spans": {name: {count, total_s,
    self_s}}, "compiles": {span: {count, seconds, cache_hits}}}``."""
    with _lock:
        return {
            "spans": {name: dict(count=int(c), total_s=t, self_s=s)
                      for name, (c, t, s) in sorted(_spans.items())},
            "compiles": {name: dict(count=int(c), seconds=t,
                                    cache_hits=int(h))
                         for name, (c, t, h) in sorted(_compiles.items())},
        }


def since(before: dict, after: Optional[dict] = None) -> dict:
    """``after`` (default: now) less ``before``, both ``snapshot()``s;
    entries that did not move are left out."""
    after = snapshot() if after is None else after
    out = {}
    for part in ("spans", "compiles"):
        out[part] = {}
        for name, now in after[part].items():
            then = before[part].get(name, {})
            moved = {k: v - then.get(k, 0) for k, v in now.items()}
            if moved["count"]:
                out[part][name] = moved
    return out


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    where = current()
    with _lock:
        agg = _compiles.setdefault(where, [0, 0.0, 0])
        agg[0] += 1
        agg[1] += duration
    with jax.profiler.TraceAnnotation(
            COMPILE_MARKER, span=where, seconds=float(duration),
            fun=str(kwargs.get("fun_name", ""))):
        pass


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        with _lock:
            _compiles.setdefault(current(), [0, 0.0, 0])[2] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
