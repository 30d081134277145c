"""``repro.analysis`` — static verification of traced round programs.

Three analyses over the jaxprs the engines already trace, none of which
runs a single algorithm round:

1. **Schedule conformance** (``schedule``) — every wire message a
   communicator prices is scope-annotated in the graph; the static
   schedule recovered from the jaxpr must equal the trace-once
   ``CommLedger`` capture, its replay expansion, and (optionally) an
   executed run's ledger, exactly.
2. **Algorithm-class certification** (``lineage``) — input-lineage
   proof that local compute reads only the machine's own feature block
   and that nothing crosses machines outside communicator primitives,
   plus Theorem 4's scalar-payload restriction for incremental inner
   rounds.
3. **Compile-hazard lints** (``lints``) — in-step RNG, group-splitting
   structure instabilities, weak-literal hazards.

Entry points: ``ExecutionPlan.audit()`` / ``plan(spec,
verify="static")`` for one cell, ``audit_registry()`` (the
``python -m repro.analysis`` CLI) for the whole registry plus the
mutation fixtures that prove the verifier rejects out-of-class
programs.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from ..core.channel import parse_channel
from ..core.comm import CommLedger
from .extract import extract_messages, trace_steps
from .findings import (AuditReport, CellAudit, Finding, FixtureResult,
                       summarize)
from .lineage import (ClassCertifier, certify_sharded_class,
                      thm4_payload_findings)
from .lints import lint_group_stability, lint_rng, lint_weak_literals
from .schedule import (verify_local_schedule, verify_sharded_schedule)

# the audited channel axis: a fixed lossless wire, a fixed quantized
# wire, and a two-stage schedule (exercises round-indexed re-pricing)
AUDIT_CHANNELS: Tuple[str, ...] = ("identity", "int8",
                                   "sched:int8@0,fp16@5")
AUDIT_PLACEMENTS: Tuple[str, ...] = ("local", "sharded")

# audit instances pin m distinct from every other dimension (m=3 vs
# d=12, d_max=4, n=12) so "axis of size m" identifies the machine axis
AUDIT_INSTANCES: Dict[str, Tuple[str, dict, dict]] = {
    # algorithm -> (instance kind, params, hyper-varied params for the
    # group-stability lint)
    "dgd": ("thm2_chain", dict(d=12, m=3, kappa=16.0),
            dict(d=12, m=3, kappa=24.0)),
    "dagd": ("thm2_chain", dict(d=12, m=3, kappa=16.0),
             dict(d=12, m=3, kappa=24.0)),
    "prox_dagd": ("thm2_chain", dict(d=12, m=3, kappa=16.0),
                  dict(d=12, m=3, kappa=24.0)),
    "bcd": ("thm2_chain", dict(d=12, m=3, kappa=16.0),
            dict(d=12, m=3, kappa=24.0)),
    "disco_f": ("thm2_chain", dict(d=12, m=3, kappa=16.0),
                dict(d=12, m=3, kappa=24.0)),
    "dsvrg": ("thm4_separable", dict(n=12, m=3, kappa=16.0),
              dict(n=12, m=3, kappa=24.0)),
}
AUDIT_ROUNDS = 8


def _ambiguous_m(dist, steps) -> bool:
    """True when some traced shape carries the machine count at a
    non-leading position — the shape convention can no longer identify
    the machine axis and class certification would be guesswork."""
    m = dist.part.m
    for ts in steps:
        jaxpr = ts.closed.jaxpr
        for v in list(jaxpr.constvars) + list(jaxpr.invars):
            shp = tuple(getattr(v.aval, "shape", ()))
            if m in shp[1:]:
                return True
    return False


def audit_plan(pl, execute: bool = False) -> CellAudit:
    """Statically audit one ``ExecutionPlan``: schedule conformance,
    class certification, and the per-cell lints.  ``execute=True`` adds
    the dynamic cross-check against an actually executed run (the eager
    python engine locally; the expanded shard_map driver sharded)."""
    from ..api.plan import PlanError  # noqa: F401  (shared error type)

    cell = CellAudit(
        algorithm=pl.algo.name if pl.algo else "",
        placement=pl.placement, channel=pl.channel,
        backend=pl.backend, engine=pl.engine,
        instance=pl.spec.instance or "")
    if pl.resolution_only:
        cell.skipped = "resolution-only plan (no instance/algorithm)"
        return cell
    if pl.faults != "none":
        cell.skipped = (f"fault injection ({pl.faults!r}) is a dynamic "
                        f"axis; static audit requires faults='none'")
        return cell
    coords = dict(algorithm=cell.algorithm, placement=cell.placement,
                  channel=cell.channel)
    chan = parse_channel(pl.wire_channel())
    if cell.placement == "sharded":
        _audit_sharded(pl, cell, chan, coords, execute)
    else:
        _audit_local(pl, cell, chan, coords, execute)
    return cell


def _stamp(findings, coords):
    return [Finding(**{**f.to_dict(), **{k: v for k, v in coords.items()
                                         if not getattr(f, k)}})
            for f in findings]


def _audit_local(pl, cell: CellAudit, chan, coords,
                 execute: bool) -> None:
    from ..core.engine import run_program

    dist, program, _ = pl._cell()
    steps = trace_steps(dist, program)
    executed_led: Optional[CommLedger] = None
    if execute:
        # the eager python engine meters every call as it happens — a
        # fully independent dynamic meter to hold the statics against
        dist.comm.ledger = executed_led = CommLedger()
        run_program(dist, program, engine="python", measure=None)
        cell.executed = True
    fs, stats = verify_local_schedule(steps, program, chan,
                                      executed_ledger=executed_led)
    cell.findings += _stamp(fs, coords)
    cell.messages = stats.get("messages", 0)
    cell.rounds = stats.get("rounds", 0)
    cell.total_bits = stats.get("total_bits", 0)
    if _ambiguous_m(dist, steps):
        cell.findings.append(Finding(
            "class-unknown", "warning",
            f"machine count m={dist.part.m} collides with another traced "
            f"dimension; the shape convention cannot identify the "
            f"machine axis, so class certification was skipped — "
            f"audit on an instance with distinct m", **coords))
    else:
        cert = ClassCertifier(dist.part.m, **coords)
        for ts in steps:
            cert.certify_step(ts)
        cell.findings += cert.findings
    if pl.algo is not None and pl.algo.incremental:
        cell.findings += thm4_payload_findings(
            steps, program, algorithm=cell.algorithm,
            channel=cell.channel)
    cell.findings += lint_rng(steps, algorithm=cell.algorithm,
                              channel=cell.channel)
    cell.findings += lint_weak_literals(steps,
                                        algorithm=cell.algorithm,
                                        channel=cell.channel)


def _audit_sharded(pl, cell: CellAudit, chan, coords,
                   execute: bool) -> None:
    # the module execute() runs, in-scan measure included
    program = pl._sharded_program(engine="scan")
    closed = program.trace()
    led, spans = program.ledger, program.spans
    executed_led: Optional[CommLedger] = None
    if execute:
        _, _, executed_led = program(CommLedger())
        cell.executed = True
    fs, stats = verify_sharded_schedule(closed, led, spans, chan,
                                        executed_ledger=executed_led)
    cell.findings += _stamp(fs, coords)
    cell.messages = stats.get("messages", 0)
    cell.rounds = stats.get("rounds", 0)
    cell.total_bits = stats.get("total_bits", 0)
    cell.findings += certify_sharded_class(
        closed, algorithm=cell.algorithm, channel=cell.channel)


def _group_stability_findings(algo_name: str) -> list:
    """Trace the algorithm under two hyper settings; identical
    structure text is what lets ``execute_batch`` group a sweep."""
    from ..api import RunSpec
    from ..api.plan import plan

    kind, pa, pb = AUDIT_INSTANCES[algo_name]
    structs = []
    for params in (pa, pb):
        spec = RunSpec(instance=kind, instance_params=params,
                       algorithm=algo_name, rounds=AUDIT_ROUNDS,
                       placement="local", engine="scan",
                       backend="einsum", channel="identity",
                       measure="none")
        pl = plan(spec)
        dist, program, _ = pl._cell()
        structs.append([ts.structure
                        for ts in trace_steps(dist, program)])
        pl.release()
    return lint_group_stability(structs[0], structs[1],
                                algorithm=algo_name,
                                channel="identity")


def audit_registry(channels: Sequence[str] = AUDIT_CHANNELS,
                   placements: Sequence[str] = AUDIT_PLACEMENTS,
                   rounds: int = AUDIT_ROUNDS,
                   execute: bool = False,
                   fixtures: bool = True,
                   quick: bool = False) -> AuditReport:
    """The registry-wide audit the CLI and the CI leg run: every
    registered algorithm × placement × channel, plus the group-
    stability lint and the mutation fixtures."""
    import jax

    from ..api import RunSpec
    from ..api.plan import PlanError, plan
    from ..experiments.registry import ALGORITHM_REGISTRY
    from .fixtures import run_fixtures

    if quick:
        channels = tuple(channels[:1]) + tuple(
            c for c in channels if c.startswith("sched:"))[:1]
        execute = False
    report = AuditReport(meta={
        "jax": jax.__version__,
        "rounds": rounds,
        "channels": list(channels),
        "placements": list(placements),
        "executed": bool(execute),
    })
    bundles: dict = {}
    for algo_name in sorted(ALGORITHM_REGISTRY):
        kind, params, _ = AUDIT_INSTANCES.get(
            algo_name, ("thm2_chain", dict(d=12, m=3, kappa=16.0),
                        None))
        for placement in placements:
            for channel in channels:
                spec = RunSpec(instance=kind, instance_params=params,
                               algorithm=algo_name, rounds=rounds,
                               placement=placement, engine="scan",
                               backend="einsum", channel=channel,
                               measure="none")
                bkey = (kind, tuple(sorted(params.items())))
                try:
                    pl = plan(spec, bundle=bundles.get(bkey))
                    bundles.setdefault(bkey, pl.bundle)
                    cell = audit_plan(pl, execute=execute)
                    pl.release()
                except PlanError as e:
                    cell = CellAudit(algorithm=algo_name,
                                     placement=placement,
                                     channel=channel, instance=kind,
                                     skipped=str(e))
                report.cells.append(cell)
        if not quick:
            try:
                stab = _group_stability_findings(algo_name)
            except PlanError as e:
                stab = [Finding("lint-group-split", "warning",
                                f"group-stability lint skipped: {e}",
                                algorithm=algo_name)]
            if stab:
                # attach to the algorithm's local/identity cell
                for cell in report.cells:
                    if cell.algorithm == algo_name \
                            and cell.placement == "local" \
                            and not cell.skipped:
                        cell.findings += stab
                        break
    if fixtures:
        report.fixtures = run_fixtures()
    return report


__all__ = [
    "AUDIT_CHANNELS", "AUDIT_INSTANCES", "AUDIT_PLACEMENTS",
    "AuditReport", "CellAudit", "ClassCertifier", "Finding",
    "FixtureResult", "audit_plan", "audit_registry",
    "certify_sharded_class", "extract_messages", "lint_group_stability",
    "lint_rng", "lint_weak_literals", "summarize",
    "thm4_payload_findings", "trace_steps", "verify_local_schedule",
    "verify_sharded_schedule",
]
