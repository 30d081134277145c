"""Typed findings and the audit report schema.

Every analysis in ``repro.analysis`` reports through one vocabulary: a
``Finding`` names the check that fired (``code``), how bad it is
(``severity``), where it fired (cell coordinates plus — when the check
anchors to a traced operation — the offending jaxpr equation and its
path), and what went wrong (``message``).  ``CellAudit`` collects one
audited (algorithm, placement, channel) cell; ``AuditReport`` is the
registry-wide result the CLI serializes to ``docs/results/
static-audit.{json,md}``.  The schema round-trips through plain dicts
(``to_dict``/``from_dict``) so served or archived audits can be
re-loaded and re-gated without re-tracing anything.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List

SEVERITIES = ("error", "warning", "info")

# the closed vocabulary of checks; gating logic and tests match on these
CODES = (
    # schedule conformance
    "sched-count",      # static message count != captured record count
    "sched-field",      # kind/shape/dtype/bits/wire/direction/tag mismatch
    "sched-round",      # message sits in the wrong round
    "sched-anchor",     # scope carries no anchoring reduce/collective op
    "sched-index",      # scope record indices non-contiguous / duplicated
    "sched-scope",      # malformed or orphaned comm scope token
    "sched-replay",     # static expansion != trace-once ledger replay
    "sched-dynamic",    # static expansion != an executed run's ledger
    # algorithm-class certification
    "class-leak",       # machine-axis slice/gather outside a comm scope
    "class-oob",        # cross-machine combination outside a comm scope
    "class-unknown",    # propagation hit an unmodeled primitive (unsound
                        # to certify past it)
    "class-measure",    # collective of the in-scan measure (repro.gap
                        # scope): measurement, not metered (info)
    "thm4-payload",     # incremental inner round ships a non-scalar
    # compile-hazard / determinism lints
    "lint-rng",         # RNG primitive inside a step jaxpr
    "lint-group-split", # same algorithm, different hypers -> different
                        # structure text (execute_batch group split)
    "lint-weak-literal",# weak-typed float literal baked into structure
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One typed analysis finding."""

    code: str
    severity: str
    message: str
    algorithm: str = ""
    placement: str = ""
    channel: str = ""
    # the offending jaxpr equation (pretty-printed, truncated) and its
    # path inside the traced program, e.g. "segment[1].eqns[7]"
    eqn: str = ""
    path: str = ""

    def __post_init__(self):
        if self.code not in CODES:
            raise ValueError(f"unknown finding code {self.code!r}")
        if self.severity not in SEVERITIES:
            raise ValueError(f"unknown severity {self.severity!r}")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Finding":
        return cls(**d)

    def __str__(self) -> str:
        where = ""
        if self.path:
            where = f" at {self.path}"
            if self.eqn:
                where += f" ({self.eqn})"
        return f"[{self.code}/{self.severity}] {self.message}{where}"


@dataclasses.dataclass
class CellAudit:
    """One audited (algorithm, placement, channel) cell."""

    algorithm: str
    placement: str
    channel: str
    backend: str = ""
    engine: str = ""
    instance: str = ""
    # static schedule stats (from the verified expansion)
    messages: int = 0          # wire messages per full run
    rounds: int = 0
    total_bits: int = 0
    findings: List[Finding] = dataclasses.field(default_factory=list)
    # non-empty when the combination is not applicable (e.g. a
    # local-only algorithm under the sharded placement) — skipped cells
    # carry the plan-time rejection and do not count as verified
    skipped: str = ""
    executed: bool = False     # dynamic (executed-run) cross-check ran

    @property
    def ok(self) -> bool:
        return not self.skipped and not any(
            f.severity == "error" for f in self.findings)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["findings"] = [f.to_dict() for f in self.findings]
        d["ok"] = self.ok
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "CellAudit":
        d = dict(d)
        d.pop("ok", None)
        d["findings"] = [Finding.from_dict(f) for f in d.get("findings", [])]
        return cls(**d)


@dataclasses.dataclass
class FixtureResult:
    """One mutation fixture: a deliberately out-of-class program that the
    verifier must reject with the expected finding code."""

    name: str
    expect_codes: List[str]
    rejected: bool
    findings: List[Finding] = dataclasses.field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["findings"] = [f.to_dict() for f in self.findings]
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FixtureResult":
        d = dict(d)
        d["findings"] = [Finding.from_dict(f) for f in d.get("findings", [])]
        return cls(**d)


@dataclasses.dataclass
class AuditReport:
    """The registry-wide static audit."""

    cells: List[CellAudit] = dataclasses.field(default_factory=list)
    fixtures: List[FixtureResult] = dataclasses.field(default_factory=list)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return (all(c.ok or c.skipped for c in self.cells)
                and all(f.rejected for f in self.fixtures))

    def errors(self) -> List[Finding]:
        return [f for c in self.cells for f in c.findings
                if f.severity == "error"]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "schema": "repro.analysis/static-audit/v1",
            "meta": self.meta,
            "ok": self.ok,
            "cells": [c.to_dict() for c in self.cells],
            "fixtures": [f.to_dict() for f in self.fixtures],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "AuditReport":
        return cls(
            cells=[CellAudit.from_dict(c) for c in d.get("cells", [])],
            fixtures=[FixtureResult.from_dict(f)
                      for f in d.get("fixtures", [])],
            meta=dict(d.get("meta", {})),
        )

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "AuditReport":
        return cls.from_dict(json.loads(text))

    # ---- markdown rendering ---------------------------------------------
    def to_markdown(self) -> str:
        lines: List[str] = []
        lines.append("# Static communication audit")
        lines.append("")
        verdict = "PASS" if self.ok else "FAIL"
        lines.append(f"**Verdict: {verdict}** — every row below is "
                     "proved from the traced jaxpr alone; `dynamic` "
                     "marks rows additionally cross-checked against an "
                     "executed run's ledger.")
        lines.append("")
        if self.meta:
            for k in sorted(self.meta):
                lines.append(f"- {k}: `{self.meta[k]}`")
            lines.append("")
        lines.append("## Schedule conformance × class certification")
        lines.append("")
        lines.append("| algorithm | placement | channel | messages | "
                     "rounds | wire bits | dynamic | status |")
        lines.append("|---|---|---|---:|---:|---:|:-:|---|")
        for c in self.cells:
            if c.skipped:
                status = f"skipped: {c.skipped}"
                stats = ("—", "—", "—")
            else:
                nerr = sum(1 for f in c.findings if f.severity == "error")
                status = "ok" if not nerr else f"{nerr} error(s)"
                stats = (str(c.messages), str(c.rounds), str(c.total_bits))
            lines.append(
                f"| {c.algorithm} | {c.placement} | `{c.channel}` | "
                f"{stats[0]} | {stats[1]} | {stats[2]} | "
                f"{'yes' if c.executed else 'no'} | {status} |")
        lines.append("")
        flagged = [(c, f) for c in self.cells for f in c.findings
                   if f.severity != "info"]
        if flagged:
            lines.append("## Findings")
            lines.append("")
            for c, f in flagged:
                lines.append(f"- `{c.algorithm}/{c.placement}/"
                             f"{c.channel}`: {f}")
            lines.append("")
        if self.fixtures:
            lines.append("## Mutation fixtures (must be rejected)")
            lines.append("")
            lines.append("| fixture | expected finding | rejected | "
                         "fired |")
            lines.append("|---|---|---|---|")
            for fx in self.fixtures:
                fired = ", ".join(sorted({f.code for f in fx.findings})) \
                    or "—"
                lines.append(
                    f"| {fx.name} | {', '.join(fx.expect_codes)} | "
                    f"{'yes' if fx.rejected else 'NO'} | {fired} |")
            lines.append("")
        return "\n".join(lines) + "\n"


def summarize(findings: List[Finding], limit: int = 3) -> str:
    """A one-line digest for exception messages."""
    errs = [f for f in findings if f.severity == "error"]
    head = "; ".join(str(f) for f in errs[:limit])
    more = len(errs) - limit
    return head + (f"; … {more} more" if more > 0 else "")


__all__ = [
    "AuditReport", "CellAudit", "Finding", "FixtureResult", "CODES",
    "SEVERITIES", "summarize",
]
