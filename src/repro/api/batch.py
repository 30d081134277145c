"""``execute_batch(plans)`` — many certification cells per compiled program.

The PR-3 scan engine compiles one XLA program per (cell, segment); a
sweep over an instance grid therefore pays one trace + compile per cell
even though every cell of the same algorithm runs the *same* round
recurrence on different data.  This module groups same-shaped cells and
``vmap``s the scan-compiled round program across the grid, so a
thm2-style sweep compiles a handful of XLA programs instead of one per
cell.

**How a cell becomes batchable.**  A cell's step function closes over
its own data (``A_stk``, masks, hyper-parameter scalars).  Each
distinct step is traced once (``core.engine.trace_segments``, the
capture every trace-once engine shares) and the result split into

  * the *structure* — the jaxpr with its constants abstracted out, and
  * the *consts* — the closed-over arrays, in trace order.

Two cells group iff their structures are string-identical (same
algorithm, same shapes, every cell-varying value hoisted into consts —
the algorithm builders wrap their scalar hypers in ``jnp.float32`` for
exactly this reason) and their consts line up shape-for-shape.  The
group then runs as ONE jitted ``lax.scan`` whose body ``vmap``s the
shared structure over the stacked consts/carries.  Anything that fails
the structural check — a python-float literal that differs per cell, a
different round budget, the python engine — falls back to the sequential
``ExecutionPlan.execute`` path.  Grouping is checked, never assumed:
a structural mismatch can only cause a fallback, not a wrong result.

**Ledger contract.**  The batched run meters nothing from compiled code;
like the scan engine it replays each step's trace-once schedule
``count`` times per segment into each cell's own fresh ``CommLedger``.
Because the schedule comes from the same step functions the sequential
engines run, every cell's record stream is **bit-identical** to its
sequential stream (``benchmarks/api_batch.py`` gates this, along with
certification-verdict identity).  Gap series agree with the sequential
scan path up to batched-``dot_general`` reassociation (same ±1-round
eps-crossing tolerance the TPU kernels get).

**Reusable pieces.**  The splitting and the group runner are public —
``prepare_cell(plan) -> Cell | None``, ``Cell.group_key()``, and
``execute_group(cells, runner_cache=...)`` — so long-lived callers
(``repro.serve``, the continuous-batching certification service) can
coalesce cells by the same key and keep the jitted group runners alive
across calls.  A ``runner_cache`` entry is sound to reuse for any batch
sharing the group key: the key covers the jaxpr structure text and every
const's shape/dtype, so evaluating a later batch's consts through the
first-seen structure performs the identical computation.
``execute_batch`` below stays the one-shot front door built from the
same pieces.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.comm import CommLedger, inject_crash_recovery
from ..core.engine import (GAP_SCOPE, Capture, _segment_xs, active_faults,
                           capture, count_fold, fold_measure, scan_input,
                           scheduled_channel, trace_segments)
from ..metrics.spans import span
from .plan import ExecutionPlan, PlanError, RunResult


@dataclasses.dataclass
class Cell:
    """One batchable certification cell: a plan traced into pure
    structure + hoisted consts, ready to group and ``vmap``."""

    plan: ExecutionPlan
    dist: object
    program: object                   # as ``fold_measure`` leaves it
    steps: List[Capture]              # one per segment (shared by identity)
    meas: Optional[Capture]
    folded: bool                      # rounds emit (w_k, A_j w_k)

    def group_key(self) -> tuple:
        """The grouping axis: cells batch iff their keys are equal.

        Composition (pinned by ``tests/test_api.py``): the leading
        components are the explicit axes — algorithm name, oracle
        backend, channel, round budget — followed by the per-segment
        (jaxpr structure text, scan length, xs shape/dtype, const
        shapes/dtypes) and the measurement structure.  The placement and
        engine axes never appear because only local/scan plans produce a
        Cell at all (``prepare_cell`` returns None otherwise).  A future
        execution axis MUST land here, or incompatible cells would
        silently merge."""
        segs = tuple(
            (conv.structure, seg.count, _segment_xs(seg).shape,
             _segment_xs(seg).dtype.str,
             tuple((tuple(c.shape), jnp.asarray(c).dtype.str)
                   for c in conv.consts))
            for seg, conv in zip(self.program.segments, self.steps))
        meas = (self.meas.structure,
                tuple((tuple(c.shape), jnp.asarray(c).dtype.str)
                      for c in self.meas.consts)) if self.meas else None
        # The channel component is the WIRE channel (the canonical sched:
        # a gap spec resolved to): two specs whose wires differ — even
        # only in a stage switch round — must not merge, while a gap spec
        # may batch with the sched: it resolved to (identical transform,
        # identical pricing; each cell still replays its own schedule).
        # The faults axis is appended LAST (the channel stays component
        # 2, which tests/test_serve.py pins): two cells under different
        # fault schedules compute identical values but replay different
        # recovery streams, so they must not merge either.
        return (self.plan.algo.name, self.plan.backend,
                self.plan.wire_channel(), self.plan.spec.rounds, segs, meas,
                self.plan.faults)


def prepare_cell(plan: ExecutionPlan) -> Optional[Cell]:
    """Trace a plan's cell into structure + consts; None if unbatchable."""
    if not plan.batchable:
        return None
    with span("repro.prepare_cell"):
        with span("repro.cell.dist"):
            dist, program, measure_fn = plan._cell()
        with span("repro.cell.trace"):
            return _trace_cell(plan, dist, program, measure_fn)


def _trace_cell(plan: ExecutionPlan, dist, program,
                measure_fn) -> Cell:
    program, measure_fn, folded = fold_measure(program, measure_fn)
    steps, meas = trace_segments(dist, program), None
    if measure_fn is not None:
        # every registered program emits the round iterate in stacked
        # block form (m, d_max) — the same shape zeros_like_w builds —
        # and a folded one the machines' responses (m, n) beside it
        w = dist.zeros_like_w()
        meas = capture(dist, measure_fn,
                       (w, dist.zeros_like_response()) if folded else w)
        if meas.records or meas.rounds:
            raise PlanError("measure performed metered communication; "
                            "measurement must stay oracle-free")
    return Cell(plan=plan, dist=dist, program=program, steps=steps,
                meas=meas, folded=folded)


# --------------------------------------------------------------------------
# Group execution
# --------------------------------------------------------------------------

def _stack_consts(cells: Sequence[Cell], pick) -> list:
    convs = [pick(c) for c in cells]
    n = len(convs[0].consts)
    return [jnp.stack([jnp.asarray(conv.consts[k]) for conv in convs])
            for k in range(n)]


def execute_group(cells: List[Cell],
                  runner_cache: Optional[dict] = None) -> List[RunResult]:
    """Run a group of cells sharing one ``group_key`` as one ``vmap``-ed
    scan program per distinct segment structure.

    ``runner_cache`` (mutable mapping, owned by the caller) keeps the
    jitted group runners alive across calls: keys are
    ``(segment jaxpr structure, shared_xs)`` — stable across batches,
    unlike the per-call trace objects — so a long-lived service can hand
    in the same dict for every batch with this group key and pay the
    trace + compile once per (structure, batch width).  Per-cell consts
    are stacked fresh per call (they carry the data); a cached runner is
    pure structure.  Safe to share only between batches with EQUAL group
    keys — the key pins structure text and const shapes/dtypes.

    Spans (``repro.metrics.spans``): ``repro.execute_group``, with the
    group key's hash and the width, holds once per segment
    ``repro.runner`` (runner-cache lookup or build) and ``repro.run``
    (dispatch until the results are ready; a runner's first call traces
    and compiles it), then ``repro.ledger_replay``."""
    with span("repro.execute_group", key=hash(cells[0].group_key()),
              width=len(cells)):
        return _execute_group(cells, runner_cache)


def _execute_group(cells: List[Cell],
                   runner_cache: Optional[dict]) -> List[RunResult]:
    progs = [c.program for c in cells]
    carry = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[p.init for p in progs])
    meas0 = cells[0].meas
    # all cells in a group share the wire channel (group_key pins it)
    sched_chan = scheduled_channel(cells[0].dist)
    runners = runner_cache if runner_cache is not None else {}
    consts_cache, outs = {}, []
    mconsts = _stack_consts(cells, lambda c: c.meas) if meas0 else []
    round_base = 0     # global round index of the next segment's start
    for s, seg0 in enumerate(progs[0].segments):
        conv0 = cells[0].steps[s]
        cell_xs = [_segment_xs(c.program.segments[s]) for c in cells]
        # the common case (index aranges, shared momentum/RNG schedules):
        # every cell scans the same xs — share one copy and broadcast it
        # across the vmap instead of scanning a (count, C) stack
        shared_xs = all(np.array_equal(x, cell_xs[0]) for x in cell_xs[1:])
        # consts are per-call values, keyed by trace identity (two steps
        # with identical structure may hoist different const VALUES);
        # runners are pure structure, keyed by the structure text so they
        # survive across calls through runner_cache
        ckey = (id(conv0), shared_xs)
        if ckey not in consts_cache:
            consts_cache[ckey] = _stack_consts(cells, lambda c: c.steps[s])
        consts = consts_cache[ckey]
        rkey = (conv0.structure, shared_xs, sched_chan is not None)
        with span("repro.runner"):
            if rkey not in runners:
                runners[rkey] = _group_runner(
                    conv0.pure, meas0.pure if meas0 else None, shared_xs,
                    sched_chan is not None)
        xs = cell_xs[0] if shared_xs else np.stack(cell_xs, axis=1)
        rounds_per_step = conv0.schedule[1]
        xs_arg = scan_input(jnp.asarray(xs), seg0.count, round_base,
                            rounds_per_step, sched_chan is not None)
        round_base += rounds_per_step * seg0.count
        with span("repro.run"):
            carry, out = jax.block_until_ready(
                runners[rkey](consts, mconsts, carry, xs_arg))
        if meas0 is not None:
            outs.append(out)                        # (count, C)
    gaps_all = np.asarray(jnp.concatenate(outs, axis=0)) if outs else None
    if cells[0].folded:     # group_key pins the steps, so all or none
        count_fold(len(cells) * len(progs[0].segments))

    # all cells in a group share the fault schedule (group_key pins it);
    # each cell's replay draws its own fault stream into its own ledger
    faults0 = active_faults(cells[0].dist)
    ledgers = []
    with span("repro.ledger_replay"):
        for cell in cells:
            ledger = CommLedger()
            for s, seg in enumerate(cell.program.segments):
                records, rounds_per_step, marks = cell.steps[s].schedule
                ledger.replay_schedule(records, rounds_per_step, marks,
                                       seg.count, channel=sched_chan,
                                       faults=faults0)
            if faults0 is not None:
                inject_crash_recovery(ledger, faults0)
            ledgers.append(ledger)
    results = []
    for i, (cell, ledger) in enumerate(zip(cells, ledgers)):
        carry_i = jax.tree.map(lambda a: a[i], carry)
        w = cell.dist.gather_w(cell.program.final(carry_i))
        pl = cell.plan
        results.append(RunResult(
            spec=pl.spec, placement=pl.placement, backend=pl.backend,
            engine=pl.engine, channel=pl.channel,
            wire_channel=pl.wire_channel(), faults=pl.faults, w=w,
            rounds=cell.program.rounds, ledger=ledger,
            gaps=gaps_all[:, i] if gaps_all is not None else None,
            budget_ok=pl._budget_ok(ledger), batched=True))
    return results


def _group_runner(pure_step, pure_meas, shared: bool, sched: bool):
    """The jitted scan of one segment over a group: the step ``vmap``-ed
    over the cells, then the gap measure (``repro.gap`` scope)."""

    def runner_fn(consts, mconsts, carry, xs):
        # scheduled channels scan (round index, per-round input) pairs;
        # the round index is identical across the batch, so it
        # broadcasts (in_axes None) like shared xs
        x_axes = ((None, None) if shared else (None, 0)) \
            if sched else (None if shared else 0)

        def body(c, x):
            c, w = jax.vmap(pure_step, in_axes=(0, 0, x_axes))(consts, c, x)
            if pure_meas is None:
                return c, None
            with jax.named_scope(GAP_SCOPE):
                return c, jax.vmap(pure_meas)(mconsts, w)

        return lax.scan(body, carry, xs)

    return jax.jit(runner_fn)


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

def execute_batch(plans: Sequence[ExecutionPlan]) -> List[RunResult]:
    """Execute many plans, vmapping groups of same-shaped cells through
    one compiled program each.  Results come back in input order; plans
    that cannot batch (python engine, sharded placement, structural
    mismatch, singleton groups) execute sequentially — batching is a
    performance optimization, never a semantic one."""
    cells: List[Optional[Cell]] = [prepare_cell(pl) for pl in plans]
    groups: dict = {}
    for i, cell in enumerate(cells):
        if cell is not None:
            groups.setdefault(cell.group_key(), []).append(i)

    results: List[Optional[RunResult]] = [None] * len(plans)
    for key, idxs in groups.items():
        if len(idxs) < 2:
            continue
        for i, res in zip(idxs, execute_group([cells[i] for i in idxs])):
            results[i] = res
    for i, res in enumerate(results):
        if res is None:
            results[i] = plans[i].execute()
    return results
