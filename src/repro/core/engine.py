"""Scan-compiled round engine: one XLA program per algorithm run.

The paper's object of study is communication *rounds* — thousands of them
per certification cell — and every algorithm in ``core.algorithms`` is a
fixed per-round recurrence.  Executing those recurrences as Python loops
costs one dispatch per op per round; compiling the whole multi-round run
into a single ``jax.lax.scan`` program is the standard JAX idiom for this
workload shape and removes both the dispatch overhead and the per-round
history materialization.

Algorithms are expressed as **round programs**:

  * a ``step(dist, carry, x) -> (carry, w_k)`` function — exactly one
    communication round: metered oracle calls, a block-local update, one
    ``dist.end_round()``, and the iterate ``w_k`` to measure this round;
  * an initial carry (a pytree of arrays, momentum scalars included);
  * ``Segment``s — a run is a sequence of (step, count[, xs]) segments so
    algorithms with non-uniform round structure (DISCO-F's Newton round
    followed by CG rounds, DSVRG's snapshot + stochastic epochs) stay
    expressible; per-round data-independent inputs (momentum coefficient
    schedules, pre-drawn sample indices) ride along as ``xs``.

Two engines execute a program:

  * ``"python"`` — one ``step`` call per round, eager dispatch.  This is
    the debugging / parity reference: it produces exactly the per-call
    oracle stream (and therefore exactly the ``CommLedger`` records) of
    the historical per-algorithm Python loops.
  * ``"scan"``  — each segment's step is traced ONCE, wrapped in
    ``lax.scan`` over the round count, and jitted, so an entire run is a
    handful of XLA programs regardless of the round budget.

**Trace-once ledger schedule.**  The ``CommLedger`` meters the paper's
communication model, and certifications must be bit-invariant to the
execution engine.  The scan engines therefore capture each segment's
step once (``trace_segment``: one ``make_jaxpr`` trace against a scratch
ledger), silence the ledger during the compiled run, and replay the
captured schedule ``count`` times into the real ledger
(``CommLedger.replay_schedule``).  Because the python engine runs the
*same* step functions, the replayed stream is bit-identical to the
per-call stream — ``tests/test_ledger_invariance`` pins this.

This module owns that contract for every engine and placement: segment
capture (``capture``/``trace_segment``), the scan body that pins the
global round index under a round-scheduled channel (``round_fn``) and
the scanned ``(round index, x)`` input it reads (``scan_input``).  The
local scan engine below, ``repro.api.batch.execute_group``,
``repro.analysis.extract.trace_steps`` and
``core.runtime.ShardedProgram`` all go through them, and all meter
through ``replay_schedule``.

**In-scan gap measurement.**  Passing ``measure`` (any traceable
``w_k -> scalar``, e.g. ``f(w_k) - f*``) folds suboptimality measurement
into the scan as a per-round scalar output: a run returns a ``(K,)``
gap series instead of a ``(K, m, d_max)`` iterate history.  ``measure``
must not call metered oracles — it is measurement, not communication
(the scan engine would bake its ops into the replayed schedule and the
python engine would meter them; either corrupts the certification).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..metrics.spans import span
from .comm import CommLedger, inject_crash_recovery
from .erm import spans_devices
from .faults import FaultRecoveryError


# Canonical list lives in repro.api._resolve (the single resolver);
# mirrored here because this module cannot import repro.api at load time
# (repro.api.plan imports modules that import this one). tests/test_api.py
# pins equality.
ENGINES = ("python", "scan")

# The gap measure's ops carry this ``jax.named_scope`` in their HLO
# metadata (``op_name``), so a device trace can tell them from the round's.
GAP_SCOPE = "repro.gap"


def resolve_engine(engine: Optional[str] = None) -> str:
    """Resolve an engine choice to ``"python"`` or ``"scan"``.

    Delegates to the single capability resolver in ``repro.api`` (env
    var consulted at call time; ``scan`` is the production default on
    every platform, the python engine exists for debugging and parity).
    Planned runs (``repro.api.plan``) arrive at ``run_program`` with the
    choice already concrete.
    """
    # call-time import: loading repro.api at module-load time would cycle
    # (api.plan imports modules that import this one). Note this pulls
    # the whole facade package on first use, not just the leaf _resolve
    # module — safe, because by call time the chain is importable.
    from ..api import _resolve
    return _resolve.resolve_engine(engine)


@dataclasses.dataclass
class Segment:
    """``count`` identical rounds driven by one step function.

    ``step(dist, carry, x) -> (carry, w_k)`` must perform exactly one
    communication round (ending with ``dist.end_round()``) and must keep
    the carry pytree structure/shapes fixed across the segment.  ``xs``
    optionally supplies a per-round input of leading dimension ``count``
    (momentum coefficients, sample indices); when absent the step
    receives the round index within the segment.
    """

    step: Callable
    count: int
    xs: Optional[np.ndarray] = None
    name: str = ""

    def __post_init__(self):
        if self.count < 1:
            raise ValueError(f"segment {self.name!r}: count must be >= 1")
        if self.xs is not None and len(self.xs) != self.count:
            raise ValueError(
                f"segment {self.name!r}: xs leading dim "
                f"{len(self.xs)} != count {self.count}")


@dataclasses.dataclass
class RoundProgram:
    """An algorithm run: initial carry, round segments, final extractor."""

    init: Any                        # carry pytree
    segments: List[Segment]
    final: Callable                  # carry -> final iterate w

    @property
    def rounds(self) -> int:
        return sum(seg.count for seg in self.segments)


@dataclasses.dataclass
class EngineResult:
    w: Any                           # final iterate (stacked blocks / local)
    rounds: int
    gaps: Optional[np.ndarray] = None      # (K,) when measure was given
    iterates: Optional[list] = None        # per-round iterates (history)


class EngineSession:
    """Reusable jit + schedule caches for repeated runs of the same
    program against the same ``dist`` (e.g. benchmark repeats).  Keyed by
    step-function identity, so program builders must construct each
    distinct step once and share it across segments."""

    def __init__(self):
        self.runners = {}
        self.schedules = {}


def run_program(dist, program: RoundProgram, *, engine: Optional[str] = None,
                measure: Optional[Callable] = None, history: bool = False,
                session: Optional[EngineSession] = None) -> EngineResult:
    """Execute a round program against a ``DistERM`` backend.

    ``measure``: traceable ``w_k -> scalar`` folded into the run as a
    per-round output (the ``(K,)`` gap series).  ``history``: collect the
    raw per-round iterates instead (debugging / parity; materializes
    ``(K, m, d_max)``).  The two are mutually exclusive.
    """
    if measure is not None and history:
        raise ValueError("measure and history are mutually exclusive")
    engine = resolve_engine(engine)
    if engine == "python":
        return _run_python(dist, program, measure, history)
    return _run_scan(dist, program, measure, history,
                     session if session is not None else EngineSession())


# --------------------------------------------------------------------------
# python engine — the per-call reference
# --------------------------------------------------------------------------

def active_faults(dist):
    """The communicator's active fault schedule, if any."""
    f = getattr(getattr(dist, "comm", None), "faults", None)
    return f if f is not None and f.active else None


def _run_python(dist, program, measure, history) -> EngineResult:
    faults = active_faults(dist)
    crash_at = None
    snap = flat = None
    if faults is not None and faults.crash_round is not None \
            and faults.crash_round <= program.rounds:
        # live crash-restart: snapshot the carry on the declared cadence
        # through the real checkpoint store, so recovery replays the real
        # save/restore path (not an in-memory copy).
        from ..checkpoint import RoundSnapshotter
        crash_at = faults.crash_round
        snap = RoundSnapshotter()
        snap.save(0, program.init)
        flat = [(seg, k) for seg in program.segments
                for k in range(seg.count)]
    carry = program.init
    gaps, iterates, rounds = [], [], 0
    try:
        for seg in program.segments:
            for k in range(seg.count):
                x = seg.xs[k] if seg.xs is not None else k
                carry, w = seg.step(dist, carry, x)
                rounds += 1
                if crash_at is not None:
                    if rounds < crash_at \
                            and rounds % faults.snapshot_every == 0:
                        snap.save(rounds, carry)
                    elif rounds == crash_at:
                        carry = _recover_crash(dist, flat, faults, snap,
                                               carry)
                        crash_at = None
                if measure is not None:
                    with jax.named_scope(GAP_SCOPE):
                        gaps.append(measure(w))
                elif history:
                    iterates.append(w)
    finally:
        if snap is not None:
            snap.close()
    return EngineResult(
        w=program.final(carry), rounds=rounds,
        gaps=np.asarray(jnp.stack(gaps)) if measure is not None else None,
        iterates=iterates if history else None)


def _recover_crash(dist, flat, faults, snap, lost_carry):
    """Crash-restart after algorithm round ``k``: restore the round-``s``
    snapshot and re-execute rounds ``s+1..k`` for real, metered as
    recovery traffic (``mark_retransmit``: every record retransmit=True,
    no fresh fault draws, recovery rounds).  The channel round index is
    pinned to the round being re-executed so scheduled-channel pricing
    matches the original.  Self-healing is then *proved*: the recomputed
    carry must be bit-identical to the state the crash lost."""
    s, k = faults.crash_span(len(flat))
    carry = snap.restore(s, like=lost_carry)
    comm, led = dist.comm, dist.comm.ledger
    led.mark_retransmit = True
    try:
        for r in range(s, k):          # 0-based rounds s..k-1 == algo s+1..k
            seg, j = flat[r]
            comm.begin_round(r)
            x = seg.xs[j] if seg.xs is not None else j
            carry, _ = seg.step(dist, carry, x)
    finally:
        led.mark_retransmit = False
        comm.reset_round()
    for a, b in zip(jax.tree_util.tree_leaves(lost_carry),
                    jax.tree_util.tree_leaves(carry)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise FaultRecoveryError(
                f"crash recovery diverged: replay of rounds {s + 1}..{k} "
                f"did not reproduce the pre-crash state")
    return carry


# --------------------------------------------------------------------------
# scan engine — trace once, run compiled
# --------------------------------------------------------------------------

def _segment_xs(seg: Segment) -> np.ndarray:
    if seg.xs is not None:
        return np.asarray(seg.xs)
    return np.arange(seg.count, dtype=np.int32)


def scheduled_channel(dist):
    """The communicator's channel iff it is round-scheduled (the case
    where the scan engines must thread the round index), else None."""
    chan = getattr(getattr(dist, "comm", None), "channel", None)
    return chan if getattr(chan, "scheduled", False) else None


def scan_input(xs, count: int, round_base: int, rounds_per_step: int,
               scheduled: bool):
    """A segment's scanned input: ``xs`` itself, or under a scheduled
    channel ``(global round index, xs)`` pairs.  The index of repeat
    ``k`` is ``round_base + k * rounds_per_step``; the schedule is a
    pure function of it, so it is data-independent and precomputed."""
    if not scheduled:
        return xs
    rid = round_base + np.arange(count, dtype=np.int32) * rounds_per_step
    return jnp.asarray(rid), xs


def round_fn(dist, step: Callable, scheduled: bool) -> Callable:
    """``step`` as the scan body's ``fn(carry, x) -> (carry, w_k)``.
    Under a scheduled channel ``x`` is a ``scan_input`` pair and the
    round index is pinned (``begin_round``) for the step's channel
    transforms, so the stage switches mid-scan."""
    if not scheduled:
        return lambda carry, x: step(dist, carry, x)

    def fn(carry, rx):
        rk, x = rx
        dist.comm.begin_round(rk)
        try:
            return step(dist, carry, x)
        finally:
            dist.comm.reset_round()

    return fn


def trace_closure(fn: Callable, *example_args):
    """Trace ``fn`` once at ``example_args`` and split it into the traced
    ``ClosedJaxpr`` and ``pure(consts, *args)``, which evaluates ``fn``
    with the arrays it closes over passed in as ``consts`` (in the
    jaxpr's const order)."""
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    out_tree = jax.tree.structure(out_shape)

    def pure(consts, *args):
        out = jax.core.eval_jaxpr(closed.jaxpr, consts, *jax.tree.leaves(args))
        return jax.tree.unflatten(out_tree, out)

    return closed, pure


@dataclasses.dataclass
class Capture:
    """One trace of a metered function: the ``trace_closure`` split and
    the ledger schedule the trace recorded — its records, the rounds it
    ended and the round-boundary marks (record positions)."""

    closed: Any                      # ClosedJaxpr
    pure: Callable                   # pure(consts, *args)
    records: list
    rounds: int
    marks: list

    @property
    def consts(self) -> list:
        return self.closed.consts

    @functools.cached_property
    def structure(self) -> str:
        """The jaxpr's text, consts abstracted: equal for two traces of
        one computation on different data."""
        return str(self.closed.jaxpr)

    @property
    def schedule(self):
        """``(records, rounds, marks)``, as ``replay_schedule`` takes
        them."""
        return self.records, self.rounds, self.marks


def capture(dist, fn: Callable, *example_args) -> Capture:
    """Trace ``fn`` once against a scratch ledger.  The captured schedule
    stays fault-free (``comm._tracing``): the ledger replay injects the
    faults.  Every trace-once engine captures through here."""
    comm = dist.comm
    real, comm.ledger = comm.ledger, CommLedger()
    comm._tracing = True
    try:
        closed, pure = trace_closure(fn, *example_args)
        led = comm.ledger
    finally:
        comm.ledger = real
        comm._tracing = False
    return Capture(closed, pure, list(led.records), led.rounds,
                   list(led.round_marks))


def trace_segment(dist, seg: Segment, carry) -> Capture:
    """Capture one round of ``seg`` at ``carry``: the traced scan body
    (``round_fn``; under a scheduled channel it takes a symbolic round
    index) and the per-round schedule the segment replays."""
    scheduled = scheduled_channel(dist) is not None
    x = jnp.asarray(_segment_xs(seg)[0])
    return capture(dist, round_fn(dist, seg.step, scheduled), carry,
                   (jnp.int32(0), x) if scheduled else x)


def trace_segments(dist, program: RoundProgram) -> List[Capture]:
    """``trace_segment`` for every segment of ``program`` at its initial
    carry; segments sharing a step and an input signature share one
    capture."""
    by_key, out = {}, []
    for seg in program.segments:
        xs = _segment_xs(seg)
        key = (id(seg.step), xs.dtype.str, xs.shape[1:])
        if key not in by_key:
            by_key[key] = trace_segment(dist, seg, program.init)
        out.append(by_key[key])
    return out


# Closed-over arrays at least this large enter a compiled program as
# arguments (``hoisted_jit``); smaller ones stay embedded constants.
HOIST_BYTES = 1 << 20


def hoisted_jit(fn: Callable) -> Callable:
    """``jax.jit(fn)``, with every large array ``fn`` closes over passed
    to the compiled program as an argument.

    Captured, XLA embeds each closed-over array (a cell's A_j blocks,
    labels, the objective's data) in the program as a constant: at
    deployment sizes that copies gigabytes through lowering, compile and
    the compilation cache, and again into device memory.  Here ``fn`` is
    traced once per argument signature (``trace_closure``) and its consts
    of ``HOIST_BYTES`` or more, and those laid out over several devices
    (a constant would lose the layout), are fed back as arguments of one
    jitted evaluator.  Smaller consts (step sizes, small instances) stay
    constants: XLA folds them, so passing them in would change the f32
    bits of every small run from what ``jax.jit(fn)`` computes."""
    traced = {}

    def call(*args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple(jax.typeof(x) for x in leaves))
        if key not in traced:
            closed, pure = trace_closure(fn, *args)
            consts = list(closed.consts)
            big = [i for i, c in enumerate(consts)
                   if getattr(c, "nbytes", 0) >= HOIST_BYTES
                   or spans_devices(c)]

            def run(hoisted, *args):
                full = list(consts)
                for i, c in zip(big, hoisted):
                    full[i] = c
                return pure(full, *args)

            traced[key] = (jax.jit(run), [consts[i] for i in big])
        run, hoisted = traced[key]
        return run(hoisted, *args)

    return call


def _build_runner(dist, step: Callable, measure, history, scheduled: bool):
    collect_w = history and measure is None
    fn = round_fn(dist, step, scheduled)

    def body(carry, x):
        carry, w = fn(carry, x)
        if measure is not None:
            with jax.named_scope(GAP_SCOPE):
                return carry, measure(w)
        return carry, (w if collect_w else None)

    return hoisted_jit(lambda carry, xs: lax.scan(body, carry, xs))


def _run_scan(dist, program, measure, history,
              session: EngineSession) -> EngineResult:
    ledger = dist.comm.ledger
    chan = scheduled_channel(dist)
    faults = active_faults(dist)
    carry = program.init
    outs, rounds = [], 0
    # Spans, once per segment (``repro.metrics.spans``): ``repro.runner``
    # (schedule capture and runner lookup or build), ``repro.run``
    # (dispatch until the segment's results are ready; a runner's first
    # call traces and compiles it) and ``repro.ledger_replay``.
    for seg in program.segments:
        xs = _segment_xs(seg)
        with span("repro.runner"):
            sched_key = (seg.step, xs.dtype.str, xs.shape[1:])
            if sched_key not in session.schedules:
                session.schedules[sched_key] = trace_segment(
                    dist, seg, carry).schedule
            records, rounds_per_step, marks = session.schedules[sched_key]
            run_key = (seg.step, measure, history, chan is not None)
            runner = session.runners.get(run_key)
            if runner is None:
                runner = _build_runner(dist, seg.step, measure, history,
                                       chan is not None)
                session.runners[run_key] = runner
        # ledger.algo_rounds is exact here: every prior segment has
        # already been replayed, and recovery rounds never shift the
        # channel schedule.
        xs_arg = scan_input(jnp.asarray(xs), seg.count, ledger.algo_rounds,
                            rounds_per_step, chan is not None)
        # The compiled run records nothing: any trace-time metering goes
        # to a throwaway ledger (jit may or may not retrace — either way
        # the schedule replay below is the single source of truth).
        dist.comm.ledger = CommLedger()
        try:
            with span("repro.run"):
                carry, out = jax.block_until_ready(runner(carry, xs_arg))
        finally:
            dist.comm.ledger = ledger
        if measure is not None or history:
            outs.append(out)
        with span("repro.ledger_replay"):
            ledger.replay_schedule(records, rounds_per_step, marks,
                                   seg.count, channel=chan, faults=faults)
        rounds += seg.count
    if faults is not None:
        # splice the crash-replay traffic exactly where the live python
        # engine records it (drops/flips/stragglers were injected by the
        # replay above; values need no recovery — replay is metering, and
        # the fault model's recovery is value-transparent).
        inject_crash_recovery(ledger, faults)
    gaps = iterates = None
    if measure is not None:
        gaps = np.asarray(jnp.concatenate(outs)) if outs else np.zeros((0,))
    elif history:
        stacked = jnp.concatenate(outs, axis=0)
        iterates = [stacked[k] for k in range(stacked.shape[0])]
    return EngineResult(w=program.final(carry), rounds=rounds,
                        gaps=gaps, iterates=iterates)
