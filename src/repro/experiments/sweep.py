"""Declarative sweep runner: instance grids x registered algorithms x eps.

A ``SweepSpec`` names an instance family, a parameter grid, the algorithms
to run, and the accuracy targets. ``run_sweep`` turns every grid cell
into a ``repro.api.RunSpec``, validates it through ``repro.api.plan``
(the single place ``auto`` backends/engines/placements resolve), executes
it through the ``CommLedger``-metered runtime — sequentially, or with
``execute="batch"`` through ``repro.api.execute_batch``, which ``vmap``s
same-shaped cells through one compiled program — measures rounds-to-eps
from the in-run per-round gap series f(w_k) - f*, and pairs each
measurement with the closed-form ``BoundReport`` the algorithm's registry
entry says must lower-bound it:

    non-incremental (F^{lam,L}), lam > 0   ->  Theorem 2
    non-incremental (F^{lam,L}), lam = 0   ->  Theorem 3
    incremental     (I^{lam,L})            ->  Theorem 4

On hard instances the record carries ``certified``: measured >= bound.
If eps was not reached within the round budget, the run still certifies
whenever budget >= bound (rounds-to-eps > budget >= bound).

Every record embeds its ``run_spec`` (the serialized RunSpec), so any
row of a ``docs/results/*.json`` report can be re-executed verbatim:

    repro.api.run(repro.api.RunSpec.from_dict(record["run_spec"]))

CLI:
    PYTHONPATH=src python -m repro.experiments.sweep --preset thm2-small
    PYTHONPATH=src python -m repro.experiments.sweep --preset all --out docs/results

Each preset writes ``docs/results/<preset>.json`` + ``<preset>.md`` and
refreshes ``docs/results/README.md``. Exit status is non-zero if any
certification fails — the harness is self-checking.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import sys
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro import api
from repro.compile_cache import enable_compile_cache

from .instances import build_instance


# --------------------------------------------------------------------------
# Spec / record / result
# --------------------------------------------------------------------------

SCHEMA_VERSION = 5      # 5: per-record error field (graceful degradation)
                        # 4: wire_channel (adaptive sched:/gap: channels)
                        # 3: bit-level accounting + channel axis (PR 5)
                        # 2: records embed their run_spec (PR 4)

# Bits one exact f32 scalar occupies: the per-round wire floor of the
# incremental family (one scalar ReduceAll per stochastic round; scalars
# bypass the channel — see core.channel).
_SCALAR_BITS = 32

Grid = Union[Dict[str, Sequence], Sequence[Dict[str, object]]]


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    name: str
    instance: str                     # key into INSTANCE_BUILDERS
    grid: Grid                        # dict of lists (product) or list of dicts
    algorithms: Tuple[str, ...]
    eps: Tuple[float, ...] = (1e-6,)
    eps_mode: str = "abs"             # "abs" | "rel" (x (f(0) - f*))
    max_rounds: int = 3000
    mode: str = "to_eps"              # "to_eps" | "fixed_rounds"
    fixed_rounds: int = 20
    note: str = ""

    def grid_points(self) -> List[Dict[str, object]]:
        if isinstance(self.grid, dict):
            keys = list(self.grid)
            return [dict(zip(keys, vals))
                    for vals in itertools.product(*(self.grid[k]
                                                    for k in keys))]
        return [dict(pt) for pt in self.grid]

    def cell_spec(self, point: Dict[str, object], algorithm: str,
                  max_rounds: Optional[int] = None,
                  backend: Optional[str] = None,
                  engine: Optional[str] = None,
                  channel: Optional[str] = None) -> api.RunSpec:
        """The RunSpec for one (grid point, algorithm) cell."""
        fixed = self.mode == "fixed_rounds"
        return api.RunSpec(
            instance=self.instance, instance_params=point,
            algorithm=algorithm,
            rounds=(self.fixed_rounds if fixed
                    else (max_rounds or self.max_rounds)),
            eps=(() if fixed else self.eps), eps_mode=self.eps_mode,
            measure=("none" if fixed else "gap"),
            backend=backend or "auto", engine=engine or "auto",
            channel=channel or "auto",
            tag=self.name)


@dataclasses.dataclass
class SweepRecord:
    instance_kind: str
    instance_label: str
    instance_params: Dict[str, float]
    hard: bool
    algorithm: str
    family: str
    incremental: bool
    accelerated: bool
    eps: Optional[float]              # as specified (rel or abs)
    eps_abs: Optional[float]
    measured_rounds: Optional[int]
    max_rounds: int
    bound_theorem: Optional[str]
    bound_rounds: Optional[float]
    ratio: Optional[float]            # measured / bound
    certified: Optional[bool]         # only meaningful on hard instances
    ledger_rounds: int
    bytes_per_round: float
    total_bytes: int
    op_counts: Dict[str, int]
    budget_ok: bool
    sample_model_bytes_per_round: float   # Arjevani-Shamir O(m d)/round
    oracle_backend: str = "einsum"        # compute path; never affects rounds
    engine: str = "scan"                  # round engine; never affects rounds
    run_spec: Optional[dict] = None       # the serialized RunSpec: any row
                                          # re-executes verbatim via
                                          # api.RunSpec.from_dict(...)
    # ---- bit-level accounting (schema 3) --------------------------------
    channel: str = "identity"             # wire model; identity leaves the
                                          # legacy stream bit-identical
    wire_channel: str = ""                # the channel actually driven on
                                          # the wire: == channel except for
                                          # gap: specs, which resolve to the
                                          # sched: schedule recorded here
                                          # (schema 4)
    bits_per_round: float = 0.0           # mean wire bits/round
    total_bits: int = 0                   # wire bits over the full budget
    bits_to_eps: Optional[int] = None     # wire bits of the first
                                          # measured_rounds rounds (exact,
                                          # via the ledger's round marks)
    bound_bits: Optional[float] = None    # the round bound x the per-round
                                          # payload floor at this channel's
                                          # precision (d elems for F^{lam,L},
                                          # one exact scalar for I^{lam,L})
    bits_certified: Optional[bool] = None # bits_to_eps >= bound_bits on
                                          # hard instances
    # ---- graceful degradation (schema 5) --------------------------------
    error: Optional[str] = None           # execution failure cause; an
                                          # errored cell still lands in the
                                          # report (partial results beat a
                                          # lost sweep) and fails the gate

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class SweepResult:
    spec: SweepSpec
    records: List[SweepRecord]
    command: str

    def summary(self) -> Dict[str, int]:
        applicable = [r for r in self.records if r.certified is not None]
        bits_app = [r for r in self.records if r.bits_certified is not None]
        return dict(
            records=len(self.records),
            certifiable=len(applicable),
            certified=sum(1 for r in applicable if r.certified),
            failed=sum(1 for r in applicable if not r.certified),
            bits_certifiable=len(bits_app),
            bits_certified=sum(1 for r in bits_app if r.bits_certified),
            bits_failed=sum(1 for r in bits_app if not r.bits_certified),
            errors=sum(1 for r in self.records if r.error is not None),
            # union, not sum: one record can fail several ways
            failed_records=sum(1 for r in self.records
                               if r.certified is False
                               or r.bits_certified is False
                               or r.error is not None),
        )

    def to_dict(self) -> dict:
        spec = dataclasses.asdict(self.spec)
        spec["grid"] = (self.spec.grid if isinstance(self.spec.grid, list)
                        else {k: list(v) for k, v in self.spec.grid.items()})
        return dict(schema_version=SCHEMA_VERSION, command=self.command,
                    spec=spec, summary=self.summary(),
                    records=[r.to_dict() for r in self.records])


# --------------------------------------------------------------------------
# Records from executed plans
# --------------------------------------------------------------------------

def _ledger_fields(result: api.RunResult, bundle) -> dict:
    led = result.ledger
    return dict(ledger_rounds=led.rounds,
                bytes_per_round=float(led.bytes_per_round()),
                total_bytes=int(led.total_bytes()),
                op_counts=led.op_counts(),
                budget_ok=bool(result.budget_ok),
                sample_model_bytes_per_round=float(
                    bundle.ctx.m * bundle.prob.d * 4),
                channel=result.channel,
                wire_channel=result.wire_channel or result.channel,
                bits_per_round=float(led.bits_per_round()),
                total_bits=int(led.total_bits()))


def _bound_bits(bound_rounds: Optional[float], channel: str,
                incremental: bool, d: int) -> Optional[float]:
    """The round bound scaled to wire bits: Theorem K rounds, each
    carrying at least the family's per-round payload floor at this
    channel's precision.  Non-incremental F^{lam,L} algorithms upload a
    full R^n / R^d vector per round (n >= d on every hard instance), so
    the floor is one d-element message through the channel — the
    ``d x precision`` scaling; incremental rounds carry one exact scalar
    (channels never touch scalar reductions), so the floor is 32 bits —
    a floor NO schedule can lower (the incremental bound is therefore
    invariant to every adaptive channel).

    For a round-scheduled channel the non-incremental floor is summed
    round by round — round k's payload floor is the stage active at k —
    which reduces exactly to ``bound_rounds * unit`` whenever the wire
    cost is round-invariant (fixed channels, one-entry schedules)."""
    if bound_rounds is None:
        return None
    from repro.core.channel import parse_channel
    if incremental:
        return float(bound_rounds) * _SCALAR_BITS
    ch = parse_channel(channel)
    if not getattr(ch, "scheduled", False):
        return float(bound_rounds) * ch.wire_bits(d, 4)
    whole = int(bound_rounds)
    total = float(sum(ch.wire_bits(d, 4, rnd=k) for k in range(whole)))
    frac = float(bound_rounds) - whole
    if frac > 0:
        total += frac * ch.wire_bits(d, 4, rnd=whole)
    return total


def _cell_records(spec: SweepSpec, pl: api.ExecutionPlan,
                  result: api.RunResult) -> List[SweepRecord]:
    """One record per eps threshold, all read off the cell's single
    metered run."""
    bundle, algo = pl.bundle, pl.algo
    base = dict(instance_kind=bundle.kind, instance_label=bundle.label,
                instance_params=dict(bundle.params), hard=bundle.hard,
                algorithm=algo.name, family=algo.family,
                incremental=algo.incremental, accelerated=algo.accelerated,
                oracle_backend=result.backend, engine=result.engine,
                max_rounds=pl.spec.rounds,
                run_spec=pl.spec.to_dict(),
                **_ledger_fields(result, bundle))

    if spec.mode == "fixed_rounds":
        return [SweepRecord(**base, eps=None, eps_abs=None,
                            measured_rounds=None, bound_theorem=None,
                            bound_rounds=None, ratio=None, certified=None)]

    records = []
    for eps in spec.eps:
        eps_abs = pl.eps_abs(eps)
        measured = result.measured_rounds(eps_abs)
        bound = pl.bound(eps_abs)
        bound_rounds = bound.rounds if bound else None
        ratio = (measured / bound_rounds
                 if measured and bound_rounds else None)
        bits_to_eps = (int(result.ledger.bits_through_round(measured))
                       if measured is not None else None)
        # bound against the channel actually driven on the wire (a gap:
        # spec prices as the sched: schedule it resolved to)
        bound_bits = _bound_bits(bound_rounds,
                                 result.wire_channel or result.channel,
                                 algo.incremental, bundle.prob.d)
        if not bundle.hard or bound_bits is None:
            bits_certified = None
        elif bits_to_eps is not None:
            bits_certified = bool(bits_to_eps >= bound_bits)
        else:
            # eps unreached: the run still certifies in bits whenever the
            # whole metered budget already exceeds the bound
            bits_certified = (True if base["total_bits"] >= bound_bits
                              else None)
        records.append(SweepRecord(
            **base, eps=eps, eps_abs=eps_abs, measured_rounds=measured,
            bound_theorem=bound.theorem if bound else None,
            bound_rounds=bound_rounds, ratio=ratio,
            certified=pl.certify(result, eps),
            bits_to_eps=bits_to_eps, bound_bits=bound_bits,
            bits_certified=bits_certified))
    return records


def _error_record(spec: SweepSpec, pl: api.ExecutionPlan,
                  exc: BaseException) -> SweepRecord:
    """A placeholder record for a cell whose execution failed: identity
    fields from the (already validated) plan, zeroed measurements, the
    failure cause in ``error``.  Lands in the report like any other
    record and trips the certification gate."""
    bundle, algo = pl.bundle, pl.algo
    return SweepRecord(
        instance_kind=bundle.kind, instance_label=bundle.label,
        instance_params=dict(bundle.params), hard=bundle.hard,
        algorithm=algo.name, family=algo.family,
        incremental=algo.incremental, accelerated=algo.accelerated,
        oracle_backend=pl.backend, engine=pl.engine,
        max_rounds=pl.spec.rounds, run_spec=pl.spec.to_dict(),
        eps=None, eps_abs=None, measured_rounds=None, bound_theorem=None,
        bound_rounds=None, ratio=None, certified=None,
        ledger_rounds=0, bytes_per_round=0.0, total_bytes=0,
        op_counts={}, budget_ok=False,
        sample_model_bytes_per_round=float(
            bundle.ctx.m * bundle.prob.d * 4),
        channel=pl.channel, error=f"{type(exc).__name__}: {exc}")


def run_sweep(spec: SweepSpec, max_rounds: Optional[int] = None,
              verbose: bool = False,
              backend: Optional[str] = None,
              engine: Optional[str] = None,
              channel: Optional[str] = None,
              execute: str = "sequential") -> SweepResult:
    """``backend``/``engine`` feed every cell's RunSpec ("auto" resolves
    through ``repro.api.plan`` — kernel on TPU / einsum elsewhere, scan
    by default). Both change local scheduling only; the CommLedger is
    bit-invariant to them (tests/test_ledger_invariance.py) and
    certification outcomes must agree (benchmarks/round_engine.py).

    ``channel`` feeds the fourth RunSpec axis: the wire model for
    per-machine uploads ("auto" resolves to identity).  Unlike the other
    axes it is *allowed* to change measurements — a lossy channel spends
    fewer bits per round and possibly more rounds — which is exactly the
    tradeoff ``benchmarks/comm_bits.py`` publishes; under the identity
    channel every legacy field is unchanged record-for-record.

    ``execute``: ``"sequential"`` runs one compiled program per cell;
    ``"batch"`` routes all cells through ``repro.api.execute_batch``,
    which groups same-shaped cells and ``vmap``s each group through ONE
    compiled program (``benchmarks/api_batch.py`` gates ledger/verdict
    identity between the two and publishes the speedup)."""
    if execute not in ("sequential", "batch"):
        raise ValueError(f"execute {execute!r}; expected 'sequential' or "
                         f"'batch'")

    def _plans():
        for point in spec.grid_points():
            bundle = build_instance(spec.instance, **point)
            for name in spec.algorithms:
                cell = spec.cell_spec(point, name, max_rounds=max_rounds,
                                      backend=backend, engine=engine,
                                      channel=channel)
                yield api.plan(cell, bundle=bundle)

    def _execute_one(pl):
        # graceful degradation: a failing cell yields its exception (turned
        # into an error record below) instead of losing the whole sweep
        try:
            return pl.execute()
        except Exception as e:        # noqa: BLE001 — recorded per-cell
            return e

    if execute == "batch":
        # grouping needs every cell up front — one compiled program per
        # same-shaped group is the whole point
        plans = list(_plans())
        try:
            executed = list(zip(plans, api.execute_batch(plans)))
        except Exception as e:        # noqa: BLE001 — degrade to per-cell
            print(f"[sweep] batch execution failed "
                  f"({type(e).__name__}: {e}); degrading to sequential "
                  f"per-cell execution", file=sys.stderr)
            executed = ((pl, _execute_one(pl)) for pl in plans)
    else:
        # one cell in memory at a time: execute as plans materialize
        executed = ((pl, _execute_one(pl)) for pl in _plans())

    records: List[SweepRecord] = []
    for pl, result in executed:
        if isinstance(result, BaseException):
            err = _error_record(spec, pl, result)
            pl.release()
            records.append(err)
            if verbose:
                print(f"  {err.instance_label} {err.algorithm:>9} "
                      f"ERROR {err.error}", file=sys.stderr)
            continue
        cell = _cell_records(spec, pl, result)
        pl.release()      # drop the cell's data copies before the next one
        records.extend(cell)
        if verbose:
            for r in cell:
                meas = (str(r.measured_rounds)
                        if r.measured_rounds is not None
                        else f">{r.max_rounds}")
                bnd = (f"{r.bound_rounds:.1f}" if r.bound_rounds
                       is not None else "-")
                cert = {True: "ok", False: "FAIL", None: "n/a"}[
                    r.certified]
                print(f"  {r.instance_label} {r.algorithm:>9} "
                      f"eps={r.eps} rounds={meas} bound={bnd} "
                      f"certified={cert}", file=sys.stderr)
    if spec.name in PRESETS:
        command = (f"PYTHONPATH=src python -m repro.experiments.sweep "
                   f"--preset {spec.name}")
    else:
        command = (f"repro.experiments.run_sweep(<ad-hoc SweepSpec "
                   f"{spec.name!r}>)")
    return SweepResult(spec=spec, records=records, command=command)


# --------------------------------------------------------------------------
# Presets
# --------------------------------------------------------------------------

PRESETS: Dict[str, SweepSpec] = {s.name: s for s in [
    SweepSpec(
        name="thm2-small", instance="thm2_chain",
        grid=dict(d=[96], kappa=[16.0, 64.0], lam=[0.5], m=[4]),
        algorithms=("dagd", "dgd", "disco_f"), eps=(1e-6,),
        max_rounds=2500,
        note="CPU-minutes Theorem-2 certification (acceptance preset)."),
    SweepSpec(
        name="thm2", instance="thm2_chain",
        grid=dict(d=[160], kappa=[16.0, 64.0, 256.0], lam=[0.5], m=[4]),
        algorithms=("dagd", "dgd", "disco_f"), eps=(1e-6,),
        max_rounds=3000,
        note="Theorem-2 tightness table (mirrors benchmarks/thm2_rounds)."),
    SweepSpec(
        name="thm3", instance="thm3_chain",
        grid=dict(d=[128], L=[1.0], m=[4]),
        algorithms=("dagd", "dgd", "prox_dagd"), eps=(1e-2, 1e-3),
        eps_mode="rel", max_rounds=4000,
        note="Theorem-3 smooth-convex certification; eps relative to "
             "f(0) - f* (sublinear regime)."),
    SweepSpec(
        name="thm4-small", instance="thm4_separable",
        grid=dict(n=[16], kappa=[64.0], lam=[0.5], m=[4]),
        algorithms=("dsvrg",), eps=(1e-4,), max_rounds=12000,
        note="Incremental-family certification, smallest n."),
    SweepSpec(
        name="thm4", instance="thm4_separable",
        grid=dict(n=[16, 32, 64], kappa=[64.0], lam=[0.5], m=[4]),
        algorithms=("dsvrg",), eps=(1e-4,), max_rounds=30000,
        note="Theorem-4 incremental family vs n (mirrors "
             "benchmarks/thm4_incremental)."),
    SweepSpec(
        name="m-invariance", instance="thm2_chain",
        grid=dict(d=[128], kappa=[64.0], lam=[0.5], m=[1, 2, 4, 8]),
        algorithms=("dagd",), eps=(1e-6,), max_rounds=1500,
        note="Round counts must be m-independent (the bounds hold for "
             "ANY m); across m the iterates differ only by ReduceAll "
             "summation order, so measured rounds may disagree by at "
             "most one eps-threshold quantization round "
             "(benchmarks/m_invariance.py gates the spread)."),
    SweepSpec(
        name="lasso", instance="lasso",
        grid=dict(n=[128], d=[256], m=[4], tau=[2e-3]),
        algorithms=("prox_dagd",), eps=(1e-4, 1e-6), max_rounds=2500,
        note="Composite workload: block-local prox, Thm-3 overlay as "
             "context (instance is not hard)."),
    SweepSpec(
        name="logistic", instance="logistic",
        grid=dict(n=[256], d=[96], m=[4], lam=[1e-2]),
        algorithms=("dagd", "dgd", "disco_f", "bcd"),
        eps=(1e-4, 1e-6), eps_mode="rel", max_rounds=2000,
        note="GLM workload; Thm-2 overlay as context (instance is not "
             "hard)."),
    SweepSpec(
        name="comm-cost", instance="random_ridge",
        grid=[dict(n=256, d=64, m=8), dict(n=64, d=256, m=8),
              dict(n=64, d=4096, m=8)],
        algorithms=("dagd",), mode="fixed_rounds", fixed_rounds=20,
        note="Feature-partition bytes/round (measured) vs the sample-"
             "partition O(m d)/round model of Arjevani-Shamir."),
]}


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.experiments.sweep",
        description="Run a bound-certification sweep and write JSON + "
                    "Markdown reports.")
    parser.add_argument("--preset", action="append", required=True,
                        choices=sorted(PRESETS) + ["all"],
                        help="preset name (repeatable), or 'all'")
    parser.add_argument("--out", default=None,
                        help="output directory (default: docs/results at "
                             "the repo root)")
    parser.add_argument("--max-rounds", type=int, default=None,
                        help="override the preset round budget")
    parser.add_argument("--batch", action="store_true",
                        help="execute cells through repro.api."
                             "execute_batch (same-shaped cells vmap'd "
                             "through one compiled program)")
    parser.add_argument("--backend", default=None,
                        help="REMOVED: set repro.api.RunSpec(backend=...) "
                             "— e.g. run_sweep(spec, backend='kernel') — "
                             "instead; this flag now only errors")
    parser.add_argument("--engine", default=None,
                        help="REMOVED: set repro.api.RunSpec(engine=...) "
                             "— e.g. run_sweep(spec, engine='python') — "
                             "instead; this flag now only errors")
    parser.add_argument("--channel", default=None,
                        help="wire model for per-machine uploads "
                             "(identity/fp16/bf16/int8/topk[:rho], a "
                             "round schedule 'sched:<ch>@0,<ch>@<k>,...' "
                             "or a gap-adaptive 'gap:<ch>,<ch>@<thr>,"
                             "...'); feeds RunSpec(channel=...) for "
                             "every cell. Presets are published under "
                             "identity; a lossy channel legitimately "
                             "changes measured rounds and bits")
    parser.add_argument("--frontier", action="store_true",
                        help="run the bits-to-eps frontier search "
                             "(repro.experiments.frontier) over the "
                             "named presets instead of the plain sweep: "
                             "every cell is re-run under a candidate "
                             "set of fixed + scheduled + gap-adaptive "
                             "channels and the rounds-vs-bits frontier "
                             "is published to docs/results/"
                             "bits-frontier.{json,md}")
    parser.add_argument("--no-report", action="store_true",
                        help="run and print, but write nothing")
    parser.add_argument("-q", "--quiet", action="store_true")
    args = parser.parse_args(argv)
    enable_compile_cache()

    for flag, field, value in (("--backend", "backend", args.backend),
                               ("--engine", "engine", args.engine)):
        if value is not None:
            parser.error(
                f"the {flag} flag was removed: set the axis on the "
                f"repro.api.RunSpec every sweep cell embeds — "
                f"RunSpec({field}={value!r}) — or pass "
                f"run_sweep(spec, {field}={value!r}) programmatically")

    from .report import default_results_dir, write_report

    names = sorted(PRESETS) if "all" in args.preset else args.preset
    out_dir = args.out or default_results_dir()

    if args.frontier:
        from . import frontier
        if "all" in args.preset:
            names = sorted(frontier.FRONTIER_EPS)
        try:
            cells = frontier.preset_cells(names,
                                          max_rounds=args.max_rounds)
        except ValueError as e:
            print(f"[frontier] {e}", file=sys.stderr)
            return 2
        doc = frontier.run_frontier(cells, backend=args.backend,
                                    engine=args.engine,
                                    verbose=not args.quiet)
        line = (f"[frontier] {len(doc['cells'])} cells, "
                f"{doc['summary']['certified']}/"
                f"{doc['summary']['certifiable']} points bit-certified")
        if not args.no_report:
            json_path, md_path = frontier.write_report(doc, out_dir)
            line += f" -> {json_path}, {md_path}"
        print(line)
        fails = frontier.gate_failures(doc)
        for f in fails:
            print(f"[frontier] GATE FAILED: {f}", file=sys.stderr)
        return 1 if fails else 0

    failed = 0
    for name in names:
        spec = PRESETS[name]
        if not args.quiet:
            print(f"[sweep] {name}: instance={spec.instance} "
                  f"algorithms={','.join(spec.algorithms)}",
                  file=sys.stderr)
        result = run_sweep(spec, max_rounds=args.max_rounds,
                           verbose=not args.quiet, backend=args.backend,
                           engine=args.engine, channel=args.channel,
                           execute="batch" if args.batch else "sequential")
        summ = result.summary()
        failed += summ["failed_records"]
        line = (f"[sweep] {name}: {summ['records']} records, "
                f"{summ['certified']}/{summ['certifiable']} certified, "
                f"{summ['bits_certified']}/{summ['bits_certifiable']} "
                f"bit-certified")
        if summ["errors"]:
            line += f", {summ['errors']} ERRORED"
        if not args.no_report:
            # the (possibly partial) report is written BEFORE the gate
            # exits non-zero — an errored cell never loses its siblings
            json_path, md_path = write_report(result, out_dir)
            line += f" -> {json_path}, {md_path}"
        print(line)
    if failed:
        print(f"[sweep] CERTIFICATION FAILED for {failed} record(s): a "
              f"measured round count or bit total fell below its lower "
              f"bound, or the cell errored (see per-record 'error')",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
