"""Admission: RunSpec payloads -> validated, traced, ticketed work.

The queue is the service's front door.  ``admit`` takes whatever a
client sent — a JSON string, a decoded dict, or an already-constructed
``RunSpec`` — and either returns a ``PendingRun`` (ticket assigned,
plan validated, cell traced and ready to coalesce) or raises a clear
``ValueError`` subclass:

  * ``SpecError``      — malformed JSON, wrong-typed fields, a payload
    that is not a JSON object, or a spec the planner rejects
    (``repro.api.PlanError`` is re-raised as-is; it IS a ValueError).
  * ``QueueFullError`` — admission control: the number of admitted but
    not-yet-completed runs is capped so a traffic burst degrades into
    explicit rejections, not unbounded memory growth.

Rejection happens BEFORE any compute is paid for (plan-time validation,
PR 4) and before the run enters the scheduler, so a malformed spec can
never poison a coalesced batch.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional, Union

from .. import api
from ..metrics.spans import span


class SpecError(ValueError):
    """A submission that cannot be turned into a runnable plan."""


class QuarantinedError(SpecError):
    """A spec rejected because earlier copies of it repeatedly failed to
    execute (poison-spec quarantine; see ``CertificationService``)."""


class QueueFullError(RuntimeError):
    """Admission control tripped: too many outstanding runs.

    Carries backpressure hints for the client: ``depth`` (current
    outstanding runs == the configured cap) and ``retry_after`` (the
    scheduler's coalescing deadline — by then at least one in-flight
    batch has been released, so capacity is the earliest plausible)."""

    def __init__(self, msg: str, *, depth: int = 0,
                 retry_after: float = 0.0):
        super().__init__(msg)
        self.depth = int(depth)
        self.retry_after = float(retry_after)


def parse_runspec(payload: Union[str, bytes, dict,
                                 api.RunSpec]) -> api.RunSpec:
    """Deserialize a submission payload into a RunSpec, wrapping every
    failure mode in a ``SpecError`` with the reason up front."""
    if isinstance(payload, api.RunSpec):
        return payload
    if isinstance(payload, (str, bytes)):
        try:
            payload = json.loads(payload)
        except json.JSONDecodeError as e:
            raise SpecError(f"malformed RunSpec JSON: {e}") from None
    if not isinstance(payload, dict):
        raise SpecError(f"a RunSpec payload must be a JSON object; got "
                        f"{type(payload).__name__}")
    try:
        return api.RunSpec.from_dict(payload)
    except ValueError as e:
        raise SpecError(str(e)) from None


@dataclasses.dataclass
class PendingRun:
    """One admitted spec: ticketed, planned, and (when batchable) traced
    into a ``repro.api.Cell`` ready for group coalescing.  ``cell`` is
    None for plans the batcher cannot take (python engine, sharded
    placement) — the service runs those on the sequential fallback
    path."""

    ticket: str
    client_id: str
    seq: int                          # per-client submission index
    spec: api.RunSpec
    plan: api.ExecutionPlan
    cell: Optional[api.Cell]
    arrival: float                    # injected clock, not wall time
    attempts: int = 0                 # failed execution attempts so far


class SubmissionQueue:
    """Ticket assignment + admission control + spec -> cell splitting.

    The queue does no scheduling — it turns payloads into ``PendingRun``s
    and tracks how many are outstanding (admitted minus completed).  The
    service hands each PendingRun to the coalescing scheduler and calls
    ``complete`` once its verdict is emitted.
    """

    def __init__(self, max_depth: int = 1024, retry_after: float = 0.05):
        self.max_depth = int(max_depth)
        self.retry_after = float(retry_after)
        self.outstanding = 0
        self.admitted = 0
        self.rejected = 0
        self.rejected_full = 0
        self._client_seq: Dict[str, int] = {}

    def admit(self, payload, client_id: str = "anon",
              now: float = 0.0) -> PendingRun:
        if self.outstanding >= self.max_depth:
            self.rejected += 1
            self.rejected_full += 1
            raise QueueFullError(
                f"submission queue full: {self.outstanding} outstanding "
                f"runs (max_depth={self.max_depth}); retry after "
                f"{self.retry_after:g}s",
                depth=self.outstanding, retry_after=self.retry_after)
        # the spans carry the ticket the spec gets if it is admitted
        with span("repro.admit", ticket=f"t{self.admitted + 1:06d}"):
            try:
                with span("repro.parse"):
                    spec = parse_runspec(payload)
                with span("repro.plan"):
                    pl = api.plan(spec)
                if pl.resolution_only:
                    raise SpecError(
                        "resolution-only RunSpec (no instance/algorithm); "
                        "nothing to certify")
                if pl.batchable:    # build the instance here, in its own
                    pl.bundle       # span, not inside the cell's
                cell = api.prepare_cell(pl)
            except ValueError:
                self.rejected += 1
                raise
        seq = self._client_seq.get(client_id, 0)
        self._client_seq[client_id] = seq + 1
        self.admitted += 1
        self.outstanding += 1
        return PendingRun(ticket=f"t{self.admitted:06d}",
                          client_id=client_id, seq=seq, spec=spec,
                          plan=pl, cell=cell, arrival=float(now))

    def complete(self, n: int = 1) -> None:
        self.outstanding = max(0, self.outstanding - n)
