"""``plan(spec) -> ExecutionPlan`` — validate a run before paying for it.

Planning is where every ``"auto"`` in a ``RunSpec`` becomes a concrete
choice (one resolver, ``repro.api._resolve``, consulted at plan time;
``auto`` placement shards an instance one device cannot hold) and where
incompatible combinations are rejected eagerly: unknown algorithm or
instance names, instance parameters the builder does not accept, eps
thresholds without measurement, gap measurement under the sharded
placement outside the scan engine (the one that measures inside the
``shard_map`` program), hyper-parameter overrides the algorithm's
program does not take.  A failed plan costs microseconds; a failed run
costs a compile.

An ``ExecutionPlan`` then drives the existing machinery:

  * ``execute()`` — one metered run through ``LocalDistERM`` +
    ``run_program`` (or a ``core.runtime.ShardedProgram``, compiled once
    per plan, for the sharded placement), returning a ``RunResult`` with
    the final iterate, the per-round gap series, and a fresh
    ``CommLedger``.
  * ``bound(eps_abs)`` — the closed-form theorem report certifying this
    (instance, algorithm) pair: Thm 2 (λ>0) / Thm 3 (λ=0) for the
    non-incremental family, Thm 4 for the incremental one.
  * ``execute_batch`` (``repro.api.batch``) — many plans per compiled
    XLA program.

The instance is built lazily (``plan`` itself stays cheap); sweeps that
share one instance across algorithms pass ``bundle=`` to avoid
rebuilding reference solutions.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..core.bounds import (BoundReport, thm2_strongly_convex,
                           thm3_smooth_convex, thm4_incremental)
from ..core.comm import CommLedger
from ..core.engine import EngineSession, run_program
from ..experiments.instances import INSTANCE_BUILDERS, InstanceBundle, \
    build_instance, builds_sharded, instance_shape
from ..experiments.registry import ALGORITHM_REGISTRY, AlgorithmSpec, \
    get_algorithm
from ..metrics.spans import span
from . import _resolve
from .spec import RunSpec


class PlanError(ValueError):
    """A RunSpec that cannot execute, rejected before any compute."""


def bound_for(bundle: InstanceBundle, algo: AlgorithmSpec,
              eps_abs: float) -> Optional[BoundReport]:
    """The theorem bound certifying this (instance, algorithm) pair, as
    declared by the algorithm's registry entry."""
    p, ctx = bundle.params, bundle.ctx
    if bundle.wstar_norm is None:
        return None
    sc_theorem, smooth_theorem = algo.certifying_theorem
    theorem = sc_theorem if ctx.lam > 0 else smooth_theorem
    if theorem == "thm4":
        n_comp = int(p.get("n", bundle.prob.n))
        kappa = float(p.get("kappa", ctx.L / max(ctx.lam, 1e-30)))
        return thm4_incremental(n_comp, kappa, ctx.lam, bundle.wstar_norm,
                                eps_abs)
    if theorem == "thm2":
        kappa = float(p.get("kappa", ctx.L / ctx.lam))
        return thm2_strongly_convex(kappa, ctx.lam, bundle.wstar_norm,
                                    eps_abs)
    return thm3_smooth_convex(float(p.get("L", ctx.L)), bundle.wstar_norm,
                              eps_abs)


@dataclasses.dataclass
class RunResult:
    """One executed run: final iterate, measurements, and the meter."""

    spec: RunSpec
    placement: str
    backend: str
    engine: str
    w: jnp.ndarray                    # assembled global iterate (d,)
    rounds: int
    ledger: CommLedger
    gaps: Optional[np.ndarray] = None     # (K,) when measure == "gap"
    budget_ok: Optional[bool] = None      # None: budget check disabled
    batched: bool = False                 # executed via execute_batch group
    channel: str = "identity"             # resolved wire model (canonical)
    wire_channel: str = ""                # channel actually driven on the
                                          # wire: == channel except for
                                          # gap: specs, which resolve to a
                                          # concrete sched: before running
    faults: str = "none"                  # resolved fault schedule
                                          # (canonical core.faults name)

    def measured_rounds(self, eps_abs: float) -> Optional[int]:
        """First round k with f(w_k) - f* <= eps_abs (1-based), or None
        if the budget never reached eps."""
        if self.gaps is None:
            raise PlanError("run was executed without gap measurement "
                            "(measure='none'); no rounds-to-eps to read")
        hits = np.nonzero(self.gaps <= eps_abs)[0]
        return int(hits[0]) + 1 if hits.size else None

    def stream(self) -> List[Tuple[str, int, int, str]]:
        """The full (kind, elems, bytes, tag) CommLedger record stream —
        the quantity the conformance suites pin bit-identical across
        backends, engines, and batching."""
        return [(r.kind, r.elems, r.bytes, r.tag)
                for r in self.ledger.records]


@dataclasses.dataclass
class ExecutionPlan:
    """A validated RunSpec with every ``auto`` resolved."""

    spec: RunSpec
    placement: str
    backend: str
    engine: str
    channel: str                      # canonical name, e.g. "topk:0.1"
    measure: str                      # "gap" | "none"
    algo: Optional[AlgorithmSpec]
    faults: str = "none"              # canonical core.faults name
    _bundle: Optional[InstanceBundle] = None
    _cell_cache: Optional[tuple] = None
    _sharded: Optional[object] = None     # core.runtime.ShardedProgram
    _gap0: Optional[float] = None
    _wire: Optional[str] = None       # gap: spec resolved to sched: (lazy)

    # ---- lazy problem construction --------------------------------------
    @property
    def resolution_only(self) -> bool:
        return self.spec.instance is None

    @property
    def batchable(self) -> bool:
        """Whether ``repro.api.prepare_cell`` traces this plan into a
        ``Cell``: a runnable plan on the local placement and scan engine."""
        return (not self.resolution_only and self.placement == "local"
                and self.engine == "scan")

    @property
    def bundle(self) -> InstanceBundle:
        if self.resolution_only:
            raise PlanError("resolution-only plan (no instance); nothing "
                            "to build")
        if self._bundle is None:
            self._bundle = build_instance(self.spec.instance,
                                          mesh=self._mesh(),
                                          **self.spec.instance_params)
        return self._bundle

    def _mesh(self) -> Optional[Mesh]:
        """The machines' devices on one axis, where a sharded plan's
        builder lays its data out itself (``builds_sharded``) and the
        host has the m devices; else None, and the sharded driver shards
        a one-device A over every device as it runs."""
        if self.placement != "sharded" \
                or not builds_sharded(self.spec.instance):
            return None
        m = instance_shape(self.spec.instance,
                           self.spec.instance_params)[2]
        devices = jax.devices()
        if len(devices) < m:
            return None
        return Mesh(np.array(devices[:m]), ("model",))

    def algo_kwargs(self) -> dict:
        return dict(self.algo.make_kwargs(self.bundle.ctx),
                    **self.spec.algo_kwargs)

    def gap0(self) -> float:
        """f(0) - f*, the denominator of relative eps thresholds."""
        if self._gap0 is None:
            b = self.bundle
            if b.fstar is None:
                raise PlanError(f"instance {b.kind!r} has no reference "
                                f"optimum (fstar); relative eps and gap "
                                f"measurement are unavailable")
            self._gap0 = float(b.objective(jnp.zeros((b.prob.d,)))
                               - b.fstar)
        return self._gap0

    def eps_abs(self, eps: float) -> float:
        return eps * self.gap0() if self.spec.eps_mode == "rel" else eps

    def bound(self, eps_abs: float) -> Optional[BoundReport]:
        return bound_for(self.bundle, self.algo, eps_abs)

    # ---- gap-adaptive channel resolution ---------------------------------
    def wire_channel(self) -> str:
        """The canonical channel actually driven on the wire.

        For fixed and ``sched:`` channels this is ``self.channel``.  A
        ``gap:`` spec is resolved here — once, lazily — into a concrete
        ``sched:`` channel by probing the cell under the identity
        channel, measuring its gap series, and pinning each stage's
        switch round where the trajectory crosses the stage threshold
        (``core.channel.GapChannel.resolve``).  The probe is a
        deterministic identity run of the same cell, so re-executing a
        recorded gap-channel spec reproduces the schedule — and the wire
        bits — exactly."""
        if not self.channel.startswith("gap:"):
            return self.channel
        if self._wire is None:
            from ..core.channel import parse_channel
            gap = parse_channel(self.channel)
            probe_spec = self.spec.replace(
                channel="identity", measure="gap", placement="local",
                backend=self.backend, engine=self.engine, faults="none")
            try:
                probe = plan(probe_spec, bundle=self._bundle)
                res = probe.execute()
            except PlanError as e:
                raise PlanError(
                    f"channel {self.channel!r} needs a measurable gap "
                    f"series to resolve its schedule: {e}") from None
            self._wire = gap.resolve(res.gaps).name
        return self._wire

    def certify(self, result: "RunResult", eps: float) -> Optional[bool]:
        """The certification verdict for one eps threshold, three-valued
        exactly as the sweep reports it: ``True``/``False`` when the
        inequality measured >= bound is conclusive, ``None`` when it is
        not applicable (instance not hard, no bound) or inconclusive
        (eps unreached within a round budget still below the bound).
        When eps goes unreached but budget >= bound, the run certifies:
        rounds-to-eps > budget >= bound."""
        eps_abs = self.eps_abs(eps)
        bound = self.bound(eps_abs)
        if not self.bundle.hard or bound is None:
            return None
        measured = result.measured_rounds(eps_abs)
        if measured is not None:
            return bool(measured >= bound.rounds)
        return True if self.spec.rounds >= bound.rounds else None

    def recovery_report(self, result: "RunResult") -> dict:
        """Measured rounds-with-faults against the bound's currency plus
        the *declared* recovery budget.  The fault schedule is seeded and
        data-independent, so its recovery cost (straggler idle rounds +
        the crash replay span) is computable before the run; a healthy
        recovery layer measures exactly the declared budget — no silent
        extra traffic, no unpriced recovery."""
        from ..core.faults import parse_faults
        led = result.ledger
        f = parse_faults(self.faults)
        declared = f.declared_recovery_rounds(led.algo_rounds)
        return dict(
            faults=self.faults,
            algo_rounds=led.algo_rounds,
            wire_rounds=led.rounds,
            recovery_rounds=led.recovery_rounds,
            declared_recovery_rounds=declared,
            within_budget=led.recovery_rounds <= declared,
            retransmissions=led.retransmissions(),
            retransmit_bits=led.retransmit_bits(),
            clean_bits=led.clean_bits(),
            total_bits=led.total_bits(),
        )

    # ---- execution -------------------------------------------------------
    def _cell(self):
        """(dist, program, measure_fn) — built once, reused across
        ``execute`` calls (each call meters into a fresh ledger)."""
        if self._cell_cache is None:
            from ..core.runtime import LocalDistERM
            b = self.bundle
            dist = LocalDistERM(b.prob, b.part, backend=self.backend,
                                channel=self.wire_channel(),
                                faults=self.faults)
            program = self.algo.program(dist, rounds=self.spec.rounds,
                                        **self.algo_kwargs())
            measure_fn = None
            if self.measure == "gap":
                objective = b.objective
                if b.fstar is None:
                    raise PlanError(f"instance {b.kind!r} has no fstar; "
                                    f"run with measure='none'")
                # f32-wrapped so fstar is a hoistable const, not a
                # per-cell literal (same f32 value the weak-typed float
                # subtraction produced; see execute_batch grouping)
                fstar = jnp.float32(b.fstar)

                def measure_fn(w_stk):
                    return objective(dist.gather_w(w_stk)) - fstar

            self._cell_cache = (dist, program, measure_fn)
        return self._cell_cache

    def _budget_ok(self, ledger: CommLedger) -> Optional[bool]:
        if not self.spec.check_budget:
            return None
        try:
            ledger.assert_budget(n=self.bundle.prob.n, d=self.bundle.prob.d)
            return True
        except AssertionError:
            return False

    def audit(self, execute: bool = False):
        """Statically audit this plan's cell (``repro.analysis``):
        schedule conformance against the trace-once ledger capture and
        its replay, algorithm-class certification, and the compile-
        hazard lints.  ``execute=True`` additionally cross-checks the
        static schedule against an executed run's ledger.  Returns the
        ``CellAudit``; ``plan(spec, verify="static")`` is the raising
        front door."""
        from ..analysis import audit_plan
        return audit_plan(self, execute=execute)

    def audit_hlo_bytes(self):
        """Lower this plan's sharded cell without running it and audit
        the compiled module's collectives
        (``core.comm.collective_bytes_from_lowered``): the module must
        carry at least the collective traffic the trace-once ledger
        metered, or the wire meter is lying about the compiled program.
        The module is the one ``execute()`` runs, lowered through the
        scan driver (the python driver has no whole-program module to
        audit): with gap measurement it also holds the measure's psums
        of f(w_k) under the ``repro.gap`` scope, which are measurement,
        not communication, and are counted apart
        (``CollectiveAudit.measure_bytes_by_op``).  Returns the
        ``CollectiveAudit``; ``plan(spec, verify=("hlo-bytes",))`` is the
        raising front door."""
        if self.placement != "sharded":
            raise PlanError(
                "verify analysis 'hlo-bytes' audits the compiled XLA "
                "module's collectives; only the sharded placement lowers "
                "to collective HLO (the local placement simulates "
                "machines on one device, so its module has none) — use "
                "placement='sharded', or verify='static' for local cells")
        from ..core.comm import collective_bytes_from_lowered
        from ..core.engine import GAP_SCOPE
        program = self._sharded_program(engine="scan")
        audit = collective_bytes_from_lowered(program.lower(),
                                              measure_scope=GAP_SCOPE)
        traced = sum(r.bytes for r in program.ledger.records)
        if program.ledger.records and audit.wire_bytes < traced:
            raise PlanError(
                f"hlo-bytes audit rejected "
                f"{self.spec.algorithm}/{self.channel}: the lowered "
                f"module carries {audit.wire_bytes} collective bytes "
                f"outside the measure but the trace-once ledger "
                f"metered {traced}")
        return audit

    def release(self) -> None:
        """Drop the cached cell (dist's padded data copy, compiled-step
        closures) and bundle.  A long sweep calls this after harvesting a
        cell's records so peak memory stays one grid point, not the whole
        grid; the plan can still re-execute (everything rebuilds)."""
        self._cell_cache = None
        self._sharded = None
        self._bundle = None

    def execute(self, session: Optional[EngineSession] = None) -> RunResult:
        if self.resolution_only:
            raise PlanError("resolution-only plan; give the RunSpec an "
                            "instance and algorithm to execute it")
        with span("repro.execute"):
            if self.placement == "sharded":
                return self._execute_sharded()
            dist, program, measure_fn = self._cell()
            dist.comm.ledger = ledger = CommLedger()
            res = run_program(dist, program, engine=self.engine,
                              measure=measure_fn, session=session)
        return RunResult(
            spec=self.spec, placement=self.placement, backend=self.backend,
            engine=self.engine, channel=self.channel,
            wire_channel=self.wire_channel(), faults=self.faults,
            w=dist.gather_w(res.w), rounds=res.rounds,
            ledger=ledger, gaps=res.gaps, budget_ok=self._budget_ok(ledger))

    def _execute_sharded(self) -> RunResult:
        if self._sharded is None:
            self._sharded = self._sharded_program()
        w, gaps, led = self._sharded(CommLedger())
        return RunResult(
            spec=self.spec, placement=self.placement, backend=self.backend,
            engine=self.engine, channel=self.channel,
            wire_channel=self.wire_channel(),
            w=w, rounds=led.rounds, ledger=led,
            gaps=None if gaps is None else np.asarray(gaps),
            budget_ok=self._budget_ok(led))

    def _sharded_program(self, engine: Optional[str] = None):
        """The plan's ``ShardedProgram`` (under ``engine``, by default
        the plan's), with the in-scan measure f(w_k) - f* evaluated from
        each machine's block."""
        from ..core.runtime import ShardedProgram
        engine = self.engine if engine is None else engine
        b = self.bundle
        kwargs = self.algo_kwargs()
        measure = None
        if self.measure == "gap":
            if b.fstar is None:
                raise PlanError(f"instance {b.kind!r} has no fstar; "
                                f"run with measure='none'")
            if b.objective != b.prob.value:
                raise PlanError(
                    f"instance {b.kind!r} adds a regularizer to the ERM "
                    f"objective; the sharded gap measurement evaluates the "
                    f"ERM objective from the blocks — measure it with "
                    f"placement='local'")
            fstar = jnp.float32(b.fstar)

            def measure(dist, w_loc):
                return dist.objective(w_loc) - fstar

        if engine == "python":
            return ShardedProgram(
                b.prob, self.spec.rounds, backend=self.backend,
                engine="python", channel=self.wire_channel(),
                algorithm_body=lambda d_, r: self.algo.fn(d_, r, **kwargs))
        return ShardedProgram(
            b.prob, self.spec.rounds, backend=self.backend, engine="scan",
            channel=self.wire_channel(), measure=measure,
            program_builder=lambda d_, r: self.algo.program(d_, r,
                                                            **kwargs))


# --------------------------------------------------------------------------
# The validator
# --------------------------------------------------------------------------

def _validate_instance(spec: RunSpec) -> None:
    if spec.instance not in INSTANCE_BUILDERS:
        raise PlanError(f"unknown instance {spec.instance!r}; known: "
                        f"{sorted(INSTANCE_BUILDERS)}")
    sig = inspect.signature(INSTANCE_BUILDERS[spec.instance])
    accepted = set(sig.parameters) - {"mesh"}     # a placement, not a
    unknown = set(spec.instance_params) - accepted    # parameter
    if unknown:
        raise PlanError(
            f"instance {spec.instance!r} does not accept parameter(s) "
            f"{sorted(unknown)}; accepted: {sorted(accepted)}")


def _validate_algorithm(spec: RunSpec) -> AlgorithmSpec:
    if spec.algorithm not in ALGORITHM_REGISTRY:
        raise PlanError(f"unknown algorithm {spec.algorithm!r}; "
                        f"registered: {sorted(ALGORITHM_REGISTRY)}")
    algo = get_algorithm(spec.algorithm)
    if spec.algo_kwargs:
        sig = inspect.signature(algo.program)
        # 'dist' and 'rounds' are positions the plan itself fills — a
        # spec supplying them would pass the signature check here only to
        # die with a duplicate-argument TypeError at execute time
        reserved = {"dist", "rounds"}
        accepted = set(sig.parameters) - reserved
        unknown = set(spec.algo_kwargs) - accepted
        if unknown:
            raise PlanError(
                f"algorithm {spec.algorithm!r} takes no hyper-parameter(s) "
                f"{sorted(unknown)}; its program accepts "
                f"{sorted(accepted)}")
    return algo


VERIFY_ANALYSES = ("static", "hlo-bytes")


def _verify_analyses(verify) -> Tuple[str, ...]:
    """Normalize ``plan``'s ``verify=`` argument — ``"none"``/``None``,
    one analysis name, or an iterable of names — to a tuple of known
    analyses, rejecting anything else eagerly."""
    if verify is None or verify == "none":
        return ()
    if isinstance(verify, str):
        verify = (verify,)
    try:
        analyses = tuple(verify)
    except TypeError:
        raise PlanError(f"verify must be an analysis name or an iterable "
                        f"of names; got {type(verify).__name__} "
                        f"({verify!r})") from None
    for a in analyses:
        if a not in VERIFY_ANALYSES:
            raise PlanError(f"unknown verify mode {a!r}; expected 'none' "
                            f"or a subset of {VERIFY_ANALYSES}")
    return analyses


def plan(spec: RunSpec,
         bundle: Optional[InstanceBundle] = None,
         verify="none") -> ExecutionPlan:
    """Resolve + validate a RunSpec.  ``bundle`` optionally supplies a
    pre-built instance (sweeps share one across algorithms); it must
    match ``spec.instance``.

    ``verify=`` names the pre-flight analyses to run over the plan
    before returning it — one name or an iterable of names from
    ``VERIFY_ANALYSES`` (e.g. ``verify=("static", "hlo-bytes")``):

      * ``"static"`` — the ``repro.analysis`` audit over the traced
        cell: the plan is rejected unless its wire schedule is provably
        the ledger's, its oracles provably read only their own feature
        partition, and no compile-hazard lint fires at error severity.
        Costs one trace per distinct segment step (no rounds execute).
      * ``"hlo-bytes"`` — the collective-bytes audit of the lowered XLA
        module (sharded placement only): the compiled program must
        carry at least the collective traffic the trace-once ledger
        metered (``ExecutionPlan.audit_hlo_bytes``)."""
    analyses = _verify_analyses(verify)
    caps = _resolve.capabilities()
    shape = (instance_shape(spec.instance, spec.instance_params)
             if spec.instance is not None else None)
    try:
        placement = _resolve.resolve_placement(spec.placement, shape=shape,
                                               caps=caps)
        backend = _resolve.resolve_oracle_backend(spec.backend, caps=caps)
        engine = _resolve.resolve_engine(spec.engine)
        channel = _resolve.resolve_channel(spec.channel)
        faults = _resolve.resolve_faults(spec.faults)
    except ValueError as e:
        raise PlanError(str(e)) from None

    if faults != "none" and placement == "sharded":
        raise PlanError(
            "fault injection needs the local placement (the "
            "detect/retransmit recovery dance runs on concrete host "
            "arrays); run faulted specs with placement='local'")

    if spec.instance is None and spec.algorithm is None:
        # resolution-only: the axes are the whole request (dry-run tools)
        if analyses:
            raise PlanError(f"verify={analyses!r} needs a runnable spec; "
                            f"a resolution-only plan traces nothing to "
                            f"audit")
        return ExecutionPlan(spec=spec, placement=placement,
                             backend=backend, engine=engine,
                             channel=channel, measure="none", algo=None,
                             faults=faults)
    if spec.instance is None or spec.algorithm is None:
        raise PlanError("a runnable RunSpec needs BOTH instance and "
                        "algorithm (leave both None for a resolution-only "
                        "plan)")

    _validate_instance(spec)
    algo = _validate_algorithm(spec)
    if spec.rounds < 1:
        raise PlanError(f"rounds must be >= 1 to execute; got "
                        f"{spec.rounds}")

    measure = spec.measure
    if measure == "auto":
        measure = "gap" if spec.eps else "none"
    if spec.eps and measure == "none":
        raise PlanError("eps thresholds were requested but measure='none'; "
                        "rounds-to-eps needs the in-run gap series")
    if channel.startswith("gap:") and placement == "sharded":
        raise PlanError(
            "gap-adaptive channels need the local placement (the "
            "schedule is resolved from an identity probe's measured gap "
            "series, run locally); pin an explicit sched: channel for "
            "sharded runs")
    if placement == "sharded":
        if measure == "gap" and engine != "scan":
            raise PlanError(
                "gap measurement under the sharded placement runs inside "
                "the scan engine's shard_map program; use engine='scan' "
                "(or placement='local')")
        if algo.local_only_kwargs:
            raise PlanError(
                f"algorithm {algo.name!r} derives machine-stacked hyper-"
                f"parameters (registry local_only_kwargs); its registry "
                f"adapter only supports placement='local'")
    if bundle is not None:
        if bundle.kind != spec.instance:
            raise PlanError(f"supplied bundle is {bundle.kind!r} but the "
                            f"spec names instance {spec.instance!r}")
        # a misaligned bundle would execute a different problem than the
        # embedded run_spec records, silently breaking the "re-execute any
        # row verbatim" guarantee — reject on the stamped builder inputs
        if bundle.build_params is not None and \
                bundle.build_params != spec.instance_params:
            raise PlanError(
                f"supplied bundle was built with {bundle.build_params} "
                f"but the spec says instance_params="
                f"{spec.instance_params}; the executed problem would not "
                f"match the recorded run_spec")

    pl = ExecutionPlan(spec=spec, placement=placement, backend=backend,
                       engine=engine, channel=channel, measure=measure,
                       algo=algo, faults=faults, _bundle=bundle)
    if "static" in analyses:
        from ..analysis import summarize
        cell = pl.audit()
        if cell.skipped:
            raise PlanError(f"verify='static' cannot audit this plan: "
                            f"{cell.skipped}")
        errors = [f for f in cell.findings if f.severity == "error"]
        if errors:
            raise PlanError(
                f"static verification rejected "
                f"{spec.algorithm}/{placement}/{channel}: "
                f"{summarize(cell.findings)}")
    if "hlo-bytes" in analyses:
        pl.audit_hlo_bytes()
    return pl


def run(spec: RunSpec, bundle: Optional[InstanceBundle] = None) -> RunResult:
    """The one-call front door: ``plan`` then ``execute``."""
    return plan(spec, bundle=bundle).execute()
