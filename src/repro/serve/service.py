"""The certification service: queue -> coalesce -> compiled cache -> stream.

``CertificationService`` wires the admission queue, the continuous-
batching scheduler, and the compiled-program cache around the reusable
``repro.api`` batch machinery:

    submit(payload)  -> ticket        (validate, plan, trace the cell)
    step(now)        -> [envelope]    (execute every batch due at `now`)
    drain(now)       -> [envelope]    (flush everything still pending)

Grouped batches run through ``repro.api.execute_group`` with this
service's per-group-key runner cache, so the trace + XLA compile is paid
once per (group structure, batch width) and every later batch of that
shape is a cache hit.  Unbatchable plans (python engine, sharded
placement) execute on the sequential ``ExecutionPlan.execute`` path —
the service never changes what a spec computes, only when and with whom
it is compiled (the soak test and ``benchmarks/serve_throughput.py``
gate verdict + typed-ledger identity against direct execution).

Results stream back as ``ResultEnvelope``s — verdict per eps threshold
plus the ledger summary (rounds, payload bytes, wire bits).  Within a
client the stream preserves submission order: a client's spec that lands
in a slow group never overtakes its earlier submissions (per-client
reorder buffer, released by sequence number).

**Resilience (PR 8).**  Execution failures never lose tickets and never
take sibling runs down with them.  A grouped batch that raises degrades
per-run down a ladder: re-run sequentially (``ExecutionPlan.execute``),
then retry with exponential backoff (``max_retries`` / ``retry_backoff``),
then re-plan on the python round engine, and only then emit a
**dead-letter envelope** (``status="error"`` with the failure cause) —
which still flows through the reorder buffer, so the client stream stays
gapless and ordered even under faults.  A group key that keeps failing
trips a circuit breaker in the program cache (later batches skip the
grouped compile entirely), and a spec that exhausts the whole ladder is
**quarantined**: later submissions of the same spec are rejected at the
door with ``QuarantinedError``.  Specs that wait longer than
``spec_timeout`` before executing are dead-lettered as timeouts.

The service never reads a wall clock; every method takes ``now``.  Real
deployments pass ``time.monotonic()``, tests and benchmarks pass a
synthetic trace — the scheduling decisions are identical either way.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .. import api
from ..metrics.spans import span
from .cache import ProgramCache
from .queue import (PendingRun, QuarantinedError, SubmissionQueue,
                    parse_runspec)
from .scheduler import Batch, CoalescingScheduler


@dataclasses.dataclass
class ResultEnvelope:
    """One served verdict.  ``result`` is the full in-process RunResult
    (tests and benchmarks compare its ledger/iterate against direct
    execution); ``to_dict()`` is the wire shape — summaries only.

    Dead letters are envelopes too: ``status="error"`` with the failure
    cause in ``error`` and ``result=None``.  They occupy the run's slot
    in the per-client stream, so ordering/no-loss invariants hold for
    faulted and healthy runs alike."""

    ticket: str
    client_id: str
    seq: int
    spec: api.RunSpec
    batched: bool                     # ran in a coalesced group
    cache_hit: bool                   # compile-free (key + width seen)
    width: int                        # batch width it executed at
    arrival: float
    completed: float
    verdicts: List[dict]              # per eps: measured/bound/certified
    result: Optional[api.RunResult]
    status: str = "ok"                # "ok" | "error"
    error: Optional[str] = None       # failure cause for dead letters

    @property
    def latency(self) -> float:
        return self.completed - self.arrival

    def to_dict(self) -> dict:
        base = dict(
            status=self.status, ticket=self.ticket,
            client_id=self.client_id, seq=self.seq,
            spec=self.spec.to_dict(), batched=self.batched,
            cache_hit=self.cache_hit, width=self.width,
            latency=round(self.latency, 6))
        if self.status != "ok" or self.result is None:
            base["error"] = self.error
            return base
        led = self.result.ledger
        base.update(
            verdicts=self.verdicts,
            budget_ok=self.result.budget_ok,
            ledger=dict(rounds=led.rounds,
                        total_bytes=led.total_bytes(),
                        total_bits=led.total_bits(),
                        bits_per_round=round(led.bits_per_round(), 2),
                        op_counts=led.op_counts()))
        return base


class CertificationService:
    def __init__(self, max_batch: int = 8, max_wait: float = 0.05,
                 cache_capacity: int = 32, max_depth: int = 1024,
                 max_retries: int = 1, retry_backoff: float = 0.05,
                 spec_timeout: Optional[float] = None,
                 breaker_threshold: int = 3):
        self.queue = SubmissionQueue(max_depth=max_depth,
                                     retry_after=max_wait)
        self.scheduler = CoalescingScheduler(max_batch=max_batch,
                                             max_wait=max_wait)
        self.cache = ProgramCache(capacity=cache_capacity,
                                  breaker_threshold=breaker_threshold)
        self.max_retries = int(max_retries)
        self.retry_backoff = float(retry_backoff)
        self.spec_timeout = spec_timeout
        self.batches = 0
        self.fallbacks = 0
        self.completed = 0
        self.retries = 0
        self.dead_letters = 0
        self.breaker_skips = 0
        self.group_failures = 0
        self.engine_fallbacks = 0
        self.rejected_quarantined = 0
        # retry backlog: (due time, run) — singleton batches when due
        self._retry: List[Tuple[float, PendingRun]] = []
        # poison specs, keyed by canonical JSON: rejected at submit
        self._quarantined: Set[str] = set()
        # per-client reorder buffers: release envelopes strictly in
        # submission (seq) order so a client's stream never reorders
        self._next_seq: Dict[str, int] = {}
        self._held: Dict[str, Dict[int, ResultEnvelope]] = {}

    # ---- intake ----------------------------------------------------------
    def submit(self, payload, client_id: str = "anon",
               now: float = 0.0) -> str:
        """Admit one RunSpec payload; returns its ticket.  Raises
        ``SpecError``/``PlanError`` (ValueError) on payloads that cannot
        run, ``QuarantinedError`` for specs that previously exhausted the
        recovery ladder, and ``QueueFullError`` (with ``depth`` and
        ``retry_after`` hints) under admission control — always before
        the spec reaches a batch."""
        spec = parse_runspec(payload)
        if spec.to_json() in self._quarantined:
            self.queue.rejected += 1
            self.rejected_quarantined += 1
            raise QuarantinedError(
                "spec quarantined after repeated execution failures; "
                "resubmit after operator intervention")
        run = self.queue.admit(spec, client_id=client_id, now=now)
        self.scheduler.add(run)
        return run.ticket

    @property
    def pending(self) -> int:
        return self.scheduler.pending + len(self._retry)

    # ---- execution -------------------------------------------------------
    def step(self, now: float) -> List[ResultEnvelope]:
        """Execute every batch due at ``now`` plus every retry whose
        backoff expired; returns the envelopes released by the
        per-client reorder buffers (submission order within each
        client)."""
        return self._run_batches(
            self._due_retries(now) + self.scheduler.due(now), now)

    def drain(self, now: float) -> List[ResultEnvelope]:
        """Flush and execute everything still pending, including the
        retry backlog — a drained service holds no tickets."""
        released = self._run_batches(
            self._due_retries(now, flush=True)
            + self.scheduler.due(now, flush=True), now)
        while self._retry:            # failures during the flush re-arm
            released.extend(self._run_batches(
                self._due_retries(now, flush=True), now))
        return released

    def _due_retries(self, now: float, flush: bool = False) -> List[Batch]:
        due = [(t, r) for t, r in self._retry if flush or t <= now]
        if not due:
            return []
        self._retry = [(t, r) for t, r in self._retry
                       if not (flush or t <= now)]
        return [Batch(runs=[r]) for _, r in due]

    def _run_batches(self, batches: List[Batch],
                     now: float) -> List[ResultEnvelope]:
        released: List[ResultEnvelope] = []
        for batch in batches:
            if batch.grouped and self.cache.tripped(batch.key):
                # circuit breaker: this group shape keeps crashing the
                # compiled path — skip straight to sequential execution
                self.breaker_skips += len(batch.runs)
                for run in batch.runs:
                    released.extend(self._run_single(run, batch, now))
                continue
            if batch.grouped:
                entry, hit = self.cache.lookup(batch.key, batch.width)
                try:
                    results = api.execute_group(
                        [r.cell for r in batch.runs],
                        runner_cache=entry.runners)
                except Exception:     # degrade per-run, lose no tickets
                    self.group_failures += 1
                    self.cache.record_failure(batch.key)
                    for run in batch.runs:
                        released.extend(self._run_single(run, batch, now))
                    continue
                self.cache.record_success(batch.key)
                self.batches += 1
                for run, result in zip(batch.runs, results):
                    released.extend(self._complete(run, result, batch,
                                                   hit, now))
            else:
                for run in batch.runs:
                    released.extend(self._run_single(run, batch, now))
        return released

    def _run_single(self, run: PendingRun, batch: Batch,
                    now: float) -> List[ResultEnvelope]:
        """Sequential rung of the degradation ladder: execute one run
        alone; on failure retry with backoff, then re-plan on the python
        engine, then dead-letter + quarantine."""
        if run.cell is None and run.attempts == 0:
            self.fallbacks += 1       # unbatchable plan, healthy path
        if (self.spec_timeout is not None
                and now - run.arrival > self.spec_timeout):
            return self._dead_letter(
                run, now, f"timed out: waited {now - run.arrival:g}s "
                f"(spec_timeout={self.spec_timeout:g}s)")
        try:
            result = run.plan.execute()
        except Exception as e:        # noqa: BLE001 — ladder continues
            run.attempts += 1
            if run.attempts <= self.max_retries:
                delay = self.retry_backoff * (2 ** (run.attempts - 1))
                self._retry.append((now + delay, run))
                self.retries += 1
                return []
            result = self._python_fallback(run)
            if result is None:
                self._quarantined.add(run.spec.to_json())
                return self._dead_letter(
                    run, now, f"{type(e).__name__}: {e} "
                    f"(after {run.attempts} attempts + engine fallback)")
            self.engine_fallbacks += 1
        return self._complete(run, result, batch, False, now)

    def _python_fallback(self, run: PendingRun) -> Optional[api.RunResult]:
        """Last execution rung: re-plan the spec on the interpreted
        python round engine (no XLA compile in the loop).  Returns None
        when that also fails or the run already was on python."""
        if run.plan.engine == "python":
            return None
        try:
            fb = dataclasses.replace(run.spec, engine="python")
            return api.plan(fb).execute()
        except Exception:             # noqa: BLE001 — ladder exhausted
            return None

    def _complete(self, run: PendingRun, result: api.RunResult,
                  batch: Batch, cache_hit: bool,
                  now: float) -> List[ResultEnvelope]:
        with span("repro.verdicts", ticket=run.ticket):
            verdicts = self._verdicts(run.plan, result)
        env = ResultEnvelope(
            ticket=run.ticket, client_id=run.client_id, seq=run.seq,
            spec=run.spec, batched=batch.grouped, cache_hit=cache_hit,
            width=batch.width, arrival=run.arrival, completed=now,
            verdicts=verdicts, result=result)
        return self._release(run, env)

    def _dead_letter(self, run: PendingRun, now: float,
                     cause: str) -> List[ResultEnvelope]:
        self.dead_letters += 1
        env = ResultEnvelope(
            ticket=run.ticket, client_id=run.client_id, seq=run.seq,
            spec=run.spec, batched=False, cache_hit=False, width=1,
            arrival=run.arrival, completed=now, verdicts=[],
            result=None, status="error", error=cause)
        return self._release(run, env)

    def _release(self, run: PendingRun,
                 env: ResultEnvelope) -> List[ResultEnvelope]:
        with span("repro.release", ticket=run.ticket):
            run.plan.release()        # drop the cell's data copies
            run.cell = None
            self.queue.complete()
            self.completed += 1
            # reorder-buffer release
            held = self._held.setdefault(run.client_id, {})
            held[run.seq] = env
            nxt = self._next_seq.get(run.client_id, 0)
            out: List[ResultEnvelope] = []
            while nxt in held:
                out.append(held.pop(nxt))
                nxt += 1
            self._next_seq[run.client_id] = nxt
        return out

    @staticmethod
    def _verdicts(pl: api.ExecutionPlan, result: api.RunResult) -> List[dict]:
        out = []
        for eps in pl.spec.eps:
            eps_abs = pl.eps_abs(eps)
            bound = pl.bound(eps_abs)
            out.append(dict(
                eps=eps, measured_rounds=result.measured_rounds(eps_abs),
                bound_rounds=bound.rounds if bound else None,
                certified=pl.certify(result, eps)))
        return out

    # ---- introspection ---------------------------------------------------
    def stats(self) -> dict:
        return dict(admitted=self.queue.admitted,
                    rejected=self.queue.rejected,
                    rejected_full=self.queue.rejected_full,
                    rejected_quarantined=self.rejected_quarantined,
                    quarantined=len(self._quarantined),
                    completed=self.completed,
                    pending=self.pending,
                    batches=self.batches,
                    fallbacks=self.fallbacks,
                    retries=self.retries,
                    group_failures=self.group_failures,
                    breaker_skips=self.breaker_skips,
                    engine_fallbacks=self.engine_fallbacks,
                    dead_letters=self.dead_letters,
                    cache=self.cache.stats().to_dict())


def replay_trace(service: CertificationService, arrivals,
                 on_reject=None) -> List[ResultEnvelope]:
    """Drive a service through an arrival trace (objects with ``t``,
    ``client_id``, ``spec`` — see ``repro.serve.workload``) on the
    trace's own clock: step at each arrival time, then drain.  Fully
    deterministic for a fixed trace.  Rejections go to ``on_reject(
    arrival, error)`` when given, else re-raise."""
    envelopes: List[ResultEnvelope] = []
    last = 0.0
    for a in arrivals:
        envelopes.extend(service.step(a.t))
        last = a.t
        try:
            service.submit(a.spec, client_id=a.client_id, now=a.t)
        except (ValueError, RuntimeError) as e:
            if on_reject is None:
                raise
            on_reject(a, e)
    envelopes.extend(service.drain(last))
    return envelopes
