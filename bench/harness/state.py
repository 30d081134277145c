"""What one run of a cell gathers, from set-up to the result line."""
from __future__ import annotations

import dataclasses
import pathlib
import sys
import time
from typing import Dict, List, Optional

from .cells import Cell


class CompileClock:
    """Seconds and count of XLA backend compiles, from JAX's own
    ``jax.monitoring`` events (a program found in the persistent cache
    counts too, with the seconds of its lookup), and how many of them the
    persistent cache served."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.seconds, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def reading(self):
        return self.count, self.seconds, self.hits


def peak_bytes():
    """Peak device bytes in use on the fullest chip, where JAX reports
    it."""
    import jax
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
             for dev in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Check:
    """One number compared against the plain reference: ``value`` must
    not exceed ``limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """One process's run of one cell.  Drivers fill it; readers of
    per-layer metrics read it."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    start: float                      # time.monotonic() at process start
    work_dir: pathlib.Path            # traces, inside the checkout
    clock: Optional[CompileClock] = None
    peaks: Optional[dict] = None      # bench/peaks.json row of the device
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: List[Check] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: Optional[int] = None
    device_trace: Optional[object] = None     # harness.trace.Trace

    def phase(self, name: str, t0: float) -> None:
        """Record set-up phase ``name`` as having run since ``t0``."""
        self.phases[name] = time.monotonic() - t0

    def note(self, line: str) -> None:
        """A diagnostic line, printed before the result (stderr)."""
        print(f"[bench] {line}", file=sys.stderr, flush=True)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append(Check(name, float(value), float(limit)))

    def count_compiles(self, part: str, since=(0, 0.0, 0)) -> tuple:
        """Record the compiles since ``since`` (a ``CompileClock``
        reading) as those of ``part`` (``setup``, ``window``); returns the
        clock's reading now."""
        now = self.clock.reading()
        n, seconds, hits = (a - b for a, b in zip(now, since))
        self.counters.update({f"compiles_{part}": n,
                              f"compile_s_{part}": seconds,
                              f"cache_hits_{part}": hits})
        return now
