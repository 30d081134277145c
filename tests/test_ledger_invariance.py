"""The CommLedger must be bit-identical across oracle backends AND
round engines.

The paper's lower bounds meter communication rounds; how the per-machine
GEMVs are computed (einsum vs Pallas kernel) and how the rounds are
driven (per-call Python loop vs one scan-compiled XLA program whose
trace-once schedule is replayed) are both outside the model. If either
axis ever leaked into the meter — an extra reduce, a different payload
size, a changed tag, a mis-multiplied schedule — every certification
under docs/results/ would silently depend on it. These tests pin the
full record stream (kind, elems, bytes, tag) and the round counter, per
registered algorithm, across the {einsum, kernel, fused} x
{python, scan} product, the channel conformance matrix from the fused
round-step redesign, and the sweep-level measurement on a hard instance.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import make_random_erm
from repro.core.engine import ENGINES, run_program
from repro.core.partition import even_partition
from repro.core.runtime import ORACLE_BACKENDS, LocalDistERM
from repro.experiments.registry import ALGORITHM_REGISTRY, get_algorithm
from repro.experiments.instances import build_instance

ROUNDS = 6


def _ledger_stream(dist):
    led = dist.comm.ledger
    # the full typed stream: legacy tuple + the bit-accounting tail and
    # the round-boundary marks all must be engine/backend-invariant
    return led.rounds, led.round_marks, led.typed_stream()


def _run(algo_name: str, backend: str, engine: str = "python"):
    bundle = build_instance("random_ridge", n=24, d=32, m=4)
    algo = get_algorithm(algo_name)
    dist = LocalDistERM(bundle.prob, bundle.part, backend=backend)
    program = algo.program(dist, rounds=ROUNDS,
                           **algo.make_kwargs(bundle.ctx))
    run_program(dist, program, engine=engine)
    return _ledger_stream(dist)


@pytest.mark.parametrize("algo_name", sorted(ALGORITHM_REGISTRY))
def test_ledger_bit_identical_across_backends(algo_name):
    streams = {be: _run(algo_name, be) for be in ORACLE_BACKENDS}
    rounds0, marks0, records0 = streams["einsum"]
    assert rounds0 == ROUNDS == len(marks0)
    for be, (rounds, marks, records) in streams.items():
        assert rounds == rounds0, (algo_name, be)
        assert marks == marks0, (algo_name, be)
        assert records == records0, (algo_name, be)


@pytest.mark.parametrize("algo_name", sorted(ALGORITHM_REGISTRY))
def test_ledger_bit_identical_across_engines(algo_name):
    """{python, scan} x {einsum, kernel}: the scan engine's replayed
    trace-once schedule must reproduce the per-call stream exactly."""
    streams = {(be, eng): _run(algo_name, be, eng)
               for be in ORACLE_BACKENDS for eng in ENGINES}
    rounds0, marks0, records0 = streams[("einsum", "python")]
    assert rounds0 == ROUNDS
    for key, (rounds, marks, records) in streams.items():
        assert rounds == rounds0, (algo_name, key)
        assert marks == marks0, (algo_name, key)
        assert records == records0, (algo_name, key)


@pytest.mark.parametrize("algo_name", sorted(ALGORITHM_REGISTRY))
def test_byte_and_bit_totals_invariant_across_backends_and_engines(
        algo_name):
    """The aggregate accounting — total bytes, total wire bits, per-round
    prefix sums — is a pure function of the algorithm, never of how it
    executed."""
    totals = set()
    for be in ORACLE_BACKENDS:
        for eng in ENGINES:
            bundle = build_instance("random_ridge", n=24, d=32, m=4)
            algo = get_algorithm(algo_name)
            dist = LocalDistERM(bundle.prob, bundle.part, backend=be)
            program = algo.program(dist, rounds=ROUNDS,
                                   **algo.make_kwargs(bundle.ctx))
            run_program(dist, program, engine=eng)
            led = dist.comm.ledger
            totals.add((led.total_bytes(), led.total_bits(),
                        tuple(led.bits_through_round(k)
                              for k in range(ROUNDS + 1))))
    assert len(totals) == 1, (algo_name, totals)
    (total_bytes, total_bits, prefix), = totals
    assert total_bits == 8 * total_bytes      # identity channel wire
    assert prefix[0] == 0 and prefix[-1] == total_bits
    assert all(a <= b for a, b in zip(prefix, prefix[1:]))


def test_byte_totals_invariant_across_batching():
    """execute_batch replays the same trace-once schedules: every cell's
    byte/bit totals and round marks match its sequential run exactly."""
    from repro import api

    specs = [api.RunSpec(
        instance="thm2_chain",
        instance_params=dict(d=24, kappa=k, lam=0.5, m=4),
        algorithm=a, rounds=80, eps=(1e-3,))
        for a in ("dagd", "dgd") for k in (16.0, 64.0)]
    seq = [api.plan(s).execute() for s in specs]
    bat = api.execute_batch([api.plan(s) for s in specs])
    assert all(r.batched for r in bat)
    for s, b in zip(seq, bat):
        assert b.ledger.total_bytes() == s.ledger.total_bytes()
        assert b.ledger.total_bits() == s.ledger.total_bits()
        assert b.ledger.round_marks == s.ledger.round_marks
        assert b.stream() == s.stream()


def test_batched_fused_cells_keep_their_own_data():
    """execute_batch groups structurally identical cells and vmaps the
    shared jaxpr over per-cell hoisted consts. The fused round-step must
    expose its cell data (A block, labels, masks, step sizes) as jit
    ARGUMENTS — closure captures get baked inside the pjit equation,
    invisible to the const-hoisting split, and every grouped cell would
    silently replay the first cell's problem. Regression: batched
    iterates equal each cell's own sequential run bit-for-bit."""
    from repro import api

    for channel in ("identity", "sched:int8@0,fp16@5"):
        specs = [api.RunSpec(
            instance="thm2_chain",
            instance_params=dict(d=24, kappa=k, lam=0.5, m=4),
            algorithm="dagd", rounds=30, eps=(1e-3,),
            backend="fused", channel=channel)
            for k in (16.0, 64.0)]
        plans = [api.plan(s) for s in specs]
        batched = api.execute_batch(plans)
        assert all(r.batched for r in batched), channel
        for plan_i, bat in zip(plans, batched):
            seq = plan_i.execute()
            assert np.array_equal(np.asarray(bat.w), np.asarray(seq.w)), \
                (channel, plan_i.spec.instance_params)
            assert bat.ledger.typed_stream() == seq.ledger.typed_stream()
            assert bat.ledger.round_marks == seq.ledger.round_marks


def test_sweep_measurement_backend_invariant():
    """The certification pipeline's ledger fields and bound overlay agree
    record-by-record across backends on a hard instance. The ledger is
    invariant *by construction* (metering happens outside the compute
    path); measured rounds-to-eps additionally requires the iterates to
    agree, which is exact on CPU but may shift an eps-threshold crossing
    by a round on TPU where the MXU-tiled kernels reassociate float adds
    — hence the +/-1 tolerance on measured_rounds only."""
    from repro.experiments.sweep import SweepSpec, run_sweep

    spec = SweepSpec(
        name="ledger-invariance-probe", instance="thm2_chain",
        grid=dict(d=[24], kappa=[16.0], lam=[0.5], m=[4]),
        algorithms=("dagd",), eps=(1e-3,), max_rounds=120)
    results = {be: run_sweep(spec, backend=be) for be in ORACLE_BACKENDS}
    base = [r.to_dict() for r in results["einsum"].records]
    assert base and base[0]["measured_rounds"] is not None
    for be, result in results.items():
        got = [r.to_dict() for r in result.records]
        assert len(got) == len(base)
        for rec, ref in zip(got, base):
            rec, ref = dict(rec), dict(ref)
            assert rec.pop("oracle_backend") == be
            ref.pop("oracle_backend")
            # the embedded RunSpec names the backend it ran under by
            # construction; everything else in it must agree
            assert rec.pop("run_spec")["backend"] == be
            ref.pop("run_spec")
            assert abs(rec.pop("measured_rounds")
                       - ref.pop("measured_rounds")) <= 1, (be, rec)
            rec.pop("ratio"), ref.pop("ratio")   # follows measured_rounds
            assert rec == ref, (be, rec, ref)


def test_kernel_backend_oracle_values_match_reference():
    """Backend dispatch changes scheduling only: oracle outputs agree with
    the whole-vector ERM reference to float tolerance."""
    prob = make_random_erm(n=40, d=36, loss="logistic", lam=0.03, seed=2)
    part = even_partition(36, 3)
    w = jnp.linspace(-1.0, 1.0, 36)
    v = jnp.linspace(1.0, -1.0, 36)
    for backend in ORACLE_BACKENDS:
        dist = LocalDistERM(prob, part, backend=backend)
        w_stk, v_stk = dist.scatter_w(w), dist.scatter_w(v)
        z = dist.response(w_stk)
        np.testing.assert_allclose(z, prob.A @ w, atol=1e-5, rtol=1e-5)
        g = dist.gather_w(dist.pgrad(w_stk, z))
        np.testing.assert_allclose(g, prob.gradient(w), atol=1e-5,
                                   rtol=1e-5)
        av = dist.response(v_stk, tag="Av")
        hv = dist.gather_w(dist.phvp(v_stk, z, av))
        np.testing.assert_allclose(hv, prob.hvp(w, v), atol=1e-5,
                                   rtol=1e-5)


MATRIX_CHANNELS = ("identity", "int8", "sched:int8@0,fp16@5")


@pytest.mark.parametrize("channel", MATRIX_CHANNELS)
@pytest.mark.parametrize("algo_name", ["dgd", "dagd"])
def test_fused_conformance_matrix(algo_name, channel):
    """The fused round-step conformance matrix: {einsum, kernel, fused} x
    {python, scan} x {identity, int8, scheduled}.

    Contract (and what the fused backend is allowed to change):
      * the CommLedger stream and round marks are bit-identical in every
        cell — fusing the channel stage into the round kernel must not
        move a single metered byte;
      * measured rounds-to-eps agree within the +/-1 threshold-crossing
        tolerance the sweep invariance test already grants;
      * the fused iterates agree with the kernel iterates to f32
        rounding: the whole-round kernel and the composed kernels are
        different programs, so their sums may round differently in the
        last ulp (compiled Mosaic and interpret mode alike).
    """
    from repro import api

    eps = 1e-3
    runs = {}
    for be in ORACLE_BACKENDS:
        for eng in ENGINES:
            spec = api.RunSpec(
                instance="thm2_chain",
                instance_params=dict(d=16, kappa=16.0, lam=0.5, m=4),
                algorithm=algo_name, rounds=40, eps=(eps,),
                backend=be, engine=eng, channel=channel)
            runs[(be, eng)] = api.plan(spec).execute()

    ref = runs[("einsum", "python")]
    ref_stream = (ref.ledger.round_marks, ref.ledger.typed_stream())
    ref_rounds = ref.measured_rounds(eps)
    for key, res in runs.items():
        assert (res.ledger.round_marks,
                res.ledger.typed_stream()) == ref_stream, key
        got = res.measured_rounds(eps)
        if ref_rounds is None:
            assert got is None, key
        else:
            assert abs(got - ref_rounds) <= 1, (key, got, ref_rounds)

    for eng in ENGINES:
        fused = np.asarray(runs[("fused", eng)].w)
        kernel = np.asarray(runs[("kernel", eng)].w)
        if channel == "identity":
            # 40 rounds of last-ulp differences on O(1) iterates
            np.testing.assert_allclose(fused, kernel, atol=1e-4, rtol=1e-4)
        else:
            # Quantized channels: a 1-ulp pre-quantization difference can
            # flip a stochastic rounding decision, so iterates agree only
            # to the accumulated quantization-noise envelope; convergence
            # equivalence is pinned by the measured-rounds check above.
            np.testing.assert_allclose(fused, kernel, atol=2e-2)


def test_faulted_ledger_bit_identical_across_backends_and_engines():
    """PR 8: the fault schedule is seeded and data-independent, so the
    recovery-priced stream — NACKs, resends, straggle idle rounds, the
    crash replay span — is bit-identical across the {einsum, kernel} x
    {python, scan} product, exactly like the clean stream."""
    from repro import api

    faults = "inject:seed=4,drop=0.2,flip=0.1,straggle=0.3x1,crash=4,snap=2"
    streams = {}
    for be in ORACLE_BACKENDS:
        for eng in ENGINES:
            spec = api.RunSpec(
                instance="thm2_chain",
                instance_params=dict(d=16, kappa=16.0, lam=0.5, m=4),
                algorithm="dagd", rounds=ROUNDS, eps=(1e-2,),
                backend=be, engine=eng, faults=faults)
            led = api.plan(spec).execute().ledger
            streams[(be, eng)] = (led.rounds, led.algo_rounds,
                                  led.recovery_rounds, led.round_marks,
                                  led.typed_stream())
    ref = streams[("einsum", "python")]
    assert ref[1] == ROUNDS                  # algo rounds unchanged
    assert ref[0] == ROUNDS + ref[2]         # wire = algo + recovery
    assert any(r[-1] for r in ref[4]), "no recovery traffic injected"
    for key, got in streams.items():
        assert got == ref, key


def test_faults_none_leaves_ledger_bit_identical():
    """The faults axis must be a no-op at "none": stream, marks, and
    totals match a spec that predates the axis entirely."""
    from repro import api

    base = dict(instance="thm2_chain",
                instance_params=dict(d=16, kappa=16.0, lam=0.5, m=4),
                algorithm="dagd", rounds=ROUNDS, eps=(1e-2,))
    led_default = api.plan(api.RunSpec(**base)).execute().ledger
    led_none = api.plan(api.RunSpec(**base, faults="none")).execute().ledger
    assert led_none.typed_stream() == led_default.typed_stream()
    assert led_none.round_marks == led_default.round_marks
    assert led_none.total_bits() == led_default.total_bits()
    assert led_none.recovery_rounds == 0
    assert led_none.retransmit_bits() == 0
    assert not any(r[-1] for r in led_none.typed_stream())


# ---- the folded round: the in-scan gap rides the response pass ----------

# A local logistic cell too tall for the whole-round kernel (n = 520 pads
# past one 512-row tile), so the fused backend composes its round too;
# lam = 1e-4 keeps the gap above f32 noise for the rounds run.
FOLD_ROUNDS, FOLD_EPS = 12, (0.3, 0.03)
FOLD_ALGOS = {"dagd": {}, "dagd-fista": dict(lam=0.0), "dgd": {},
              "prox_dagd": {}}


def _fold_plans(algo, backend, engine, channel, seeds):
    from repro import api
    return [api.plan(api.RunSpec(
        instance="logistic",
        instance_params=dict(n=520, d=40, m=4, lam=1e-4, seed=s),
        algorithm=algo.split("-")[0], rounds=FOLD_ROUNDS, eps=FOLD_EPS,
        eps_mode="rel", placement="local", backend=backend, engine=engine,
        channel=channel, algo_kwargs=FOLD_ALGOS[algo])) for s in seeds]


def _counted_run(runner, plans):
    """The runs of ``plans`` under ``runner`` (``group``: one
    ``execute_group``), and how often they ticked the fold span."""
    from repro.api.batch import execute_group, prepare_cell
    from repro.core.engine import FOLD_SPAN
    from repro.metrics import spans

    before = spans.snapshot()
    if runner == "group":
        cells = [prepare_cell(p) for p in plans]
        assert len({c.group_key() for c in cells}) == 1
        results = execute_group(cells)
    else:
        results = [p.execute() for p in plans]
    moved = spans.since(before)["spans"].get(FOLD_SPAN, {})
    return results, moved.get("count", 0)


@pytest.mark.parametrize("channel", ["identity", "sched:int8@0,fp16@4"])
@pytest.mark.parametrize("runner", ["scan", "python", "group"])
@pytest.mark.parametrize("backend", ["kernel", "fused"])
@pytest.mark.parametrize("algo", sorted(FOLD_ALGOS))
def test_folded_round_matches_the_unfolded_round(monkeypatch, algo, backend,
                                                 runner, channel):
    """A measured run on a backend with the two-vector response folds the
    gap's pass over A into the round's response pass.  Against the same
    run with the capability withdrawn (today's round, and the measure's
    own pass): bit-identical iterates, the same typed ledger stream and
    marks, gaps equal to f32 rounding and the same rounds to eps, under
    the local scan engine, the python engine and ``execute_group``."""
    from repro.core.runtime import KernelBackend

    engine = "python" if runner == "python" else "scan"
    seeds = (0, 1) if runner == "group" else (0,)
    args = (algo, backend, engine, channel, seeds)
    folded, n_folded = _counted_run(runner, _fold_plans(*args))
    with monkeypatch.context() as mp:
        mp.setattr(KernelBackend, "response_pair", False)
        plans = _fold_plans(*args)
        plain, n_plain = _counted_run(runner, plans)
    assert (n_folded, n_plain) == (len(seeds), 0)     # one segment a solve
    for got, ref, pl in zip(folded, plain, plans):
        assert np.array_equal(np.asarray(got.w), np.asarray(ref.w))
        assert got.ledger.typed_stream() == ref.ledger.typed_stream()
        assert got.ledger.round_marks == ref.ledger.round_marks
        # the gap sums A x over the blocks' partials, not in one dot: a
        # few f32 ulps of f(w) apart
        ulp = np.finfo(np.float32).eps * abs(pl.bundle.fstar)
        np.testing.assert_allclose(got.gaps, ref.gaps, rtol=0, atol=8 * ulp)
        for eps in FOLD_EPS:
            want = ref.measured_rounds(pl.eps_abs(eps))
            assert want is not None
            assert got.measured_rounds(pl.eps_abs(eps)) == want


@pytest.mark.parametrize("case", ["einsum", "measure-none", "thm2-fused"])
def test_fold_stays_off_where_the_round_has_no_response_pass(case):
    """No fold without the two-vector response (``einsum``), without a
    measure, or where the whole-round kernel runs the round (the
    ``thm2-serve`` cell's fused single-tile rounds): the fold span does
    not tick, and those runs are today's."""
    from repro import api

    spec = dict(instance="logistic",
                instance_params=dict(n=520, d=40, m=4, lam=1e-4),
                algorithm="dagd", rounds=FOLD_ROUNDS, eps=FOLD_EPS,
                eps_mode="rel", placement="local", backend="kernel")
    if case == "einsum":
        spec.update(backend="einsum")
    elif case == "measure-none":
        spec.update(eps=(), measure="none")
    else:
        spec.update(instance="thm2_chain",
                    instance_params=dict(d=24, kappa=16.0, lam=0.5, m=4),
                    eps=(1e-3,), eps_mode="abs", backend="fused")
    pl = api.plan(api.RunSpec(**spec))
    if case == "thm2-fused":
        dist, _, _ = pl._cell()
        assert dist.fused_round_step(lambda x, y, g, c: (x, y)) is not None
    for runner in ("scan", "group"):
        _, n_folded = _counted_run(runner, [pl])
        assert n_folded == 0, runner
