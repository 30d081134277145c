"""Round-engine unit tests: python-vs-scan parity, trace-once ledger
schedules, in-scan gap measurement, and the runtime satellites (masked
``dot`` with shape assertion, per-round loss-term cache).

The heavier cross-product suites live in ``test_runtime_parity.py``
(engines x oracle backends x execution backends, slow-marked) and
``test_ledger_invariance.py``; this file is the fast tier-1 coverage.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import CommLedger, GLMLoss, make_random_erm
from repro.core.engine import (ENGINES, EngineSession, RoundProgram,
                               Segment, resolve_engine, run_program)
from repro.core.partition import even_partition
from repro.core.runtime import LocalDistERM
from repro.core.algorithms import PROGRAMS
from repro.experiments.registry import ALGORITHM_REGISTRY, get_algorithm
from repro.experiments.instances import build_instance

ROUNDS = 40


def _stream(dist):
    led = dist.comm.ledger
    return led.rounds, [(r.kind, r.elems, r.bytes, r.tag)
                        for r in led.records]


def _setup(n=24, d=32, m=4, loss="squared"):
    bundle = build_instance("random_ridge", n=n, d=d, m=m)
    return bundle


def _run(bundle, algo_name, engine, rounds=ROUNDS, **overrides):
    algo = get_algorithm(algo_name)
    dist = LocalDistERM(bundle.prob, bundle.part)
    kwargs = dict(algo.make_kwargs(bundle.ctx), **overrides)
    program = algo.program(dist, rounds=rounds, **kwargs)
    res = run_program(dist, program, engine=engine, history=True)
    return dist, res


# --------------------------------------------------------------------------
# engine parity (fast, per registered algorithm)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("algo_name", sorted(ALGORITHM_REGISTRY))
def test_python_scan_parity(algo_name):
    """Same iterate history, same final w, bit-identical ledger stream."""
    bundle = _setup()
    dist_py, res_py = _run(bundle, algo_name, "python")
    dist_sc, res_sc = _run(bundle, algo_name, "scan")
    assert _stream(dist_py) == _stream(dist_sc)
    assert dist_py.comm.ledger.rounds == ROUNDS
    np.testing.assert_allclose(res_py.w, res_sc.w, atol=1e-5, rtol=1e-5)
    h_py = jnp.stack(res_py.iterates)
    h_sc = jnp.stack(res_sc.iterates)
    assert h_py.shape == h_sc.shape == (ROUNDS,) + res_py.w.shape
    np.testing.assert_allclose(h_py, h_sc, atol=1e-5, rtol=1e-5)


def test_disco_f_nonuniform_rounds_parity():
    """Multiple Newton segments (non-uniform round structure): stream and
    round count reproduce the historical loop's budget split."""
    bundle = _setup(loss="squared")
    newton_steps = 3
    rounds = 20
    inner = max(1, rounds // newton_steps - 1)
    dist_py, res_py = _run(bundle, "disco_f", "python", rounds=rounds,
                           newton_steps=newton_steps)
    dist_sc, res_sc = _run(bundle, "disco_f", "scan", rounds=rounds,
                           newton_steps=newton_steps)
    assert _stream(dist_py) == _stream(dist_sc)
    assert dist_py.comm.ledger.rounds == newton_steps * (1 + inner)
    np.testing.assert_allclose(res_py.w, res_sc.w, atol=1e-5, rtol=1e-5)


def test_dsvrg_truncated_epoch_parity():
    """A round budget that truncates the final epoch: the pre-drawn index
    sequence and segment split must still match the historical loop."""
    bundle = _setup()
    n = bundle.prob.n
    rounds = 2 * n + n // 2    # snapshot + full epoch + partial epoch
    dist_py, res_py = _run(bundle, "dsvrg", "python", rounds=rounds)
    dist_sc, res_sc = _run(bundle, "dsvrg", "scan", rounds=rounds)
    assert _stream(dist_py) == _stream(dist_sc)
    assert dist_py.comm.ledger.rounds == rounds
    np.testing.assert_allclose(jnp.stack(res_py.iterates),
                               jnp.stack(res_sc.iterates),
                               atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# in-scan gap measurement
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_measure_matches_history_gaps(engine):
    """The (K,) in-scan gap series equals objective(iterate) - f* computed
    from an explicit history, and metering is untouched by measure."""
    bundle = build_instance("thm2_chain", d=24, kappa=16.0, lam=0.5, m=4)
    algo = get_algorithm("dagd")
    kwargs = algo.make_kwargs(bundle.ctx)

    dist_m = LocalDistERM(bundle.prob, bundle.part)
    program = algo.program(dist_m, rounds=ROUNDS, **kwargs)
    measure = lambda w: bundle.objective(dist_m.gather_w(w)) - bundle.fstar
    res_m = run_program(dist_m, program, engine=engine, measure=measure)
    assert res_m.gaps.shape == (ROUNDS,)

    dist_h = LocalDistERM(bundle.prob, bundle.part)
    program = algo.program(dist_h, rounds=ROUNDS, **kwargs)
    res_h = run_program(dist_h, program, engine=engine, history=True)
    ref = np.asarray([float(bundle.objective(dist_h.gather_w(w)))
                      - bundle.fstar for w in res_h.iterates])
    np.testing.assert_allclose(res_m.gaps, ref, atol=1e-6, rtol=1e-5)
    # measurement is not communication
    assert _stream(dist_m) == _stream(dist_h)


def test_measure_and_history_exclusive():
    bundle = _setup()
    dist = LocalDistERM(bundle.prob, bundle.part)
    program = PROGRAMS["dgd"](dist, 4, L=bundle.ctx.L, lam=bundle.ctx.lam)
    with pytest.raises(ValueError):
        run_program(dist, program, measure=lambda w: 0.0, history=True)


def test_session_reuse_skips_retrace():
    """A warm EngineSession reuses jitted runners and captured schedules;
    the ledger still grows by the full per-round stream each run."""
    bundle = _setup()
    dist = LocalDistERM(bundle.prob, bundle.part)
    program = PROGRAMS["dagd"](dist, ROUNDS, L=bundle.ctx.L,
                               lam=bundle.ctx.lam)
    session = EngineSession()
    run_program(dist, program, engine="scan", session=session)
    n_runners = len(session.runners)
    first = _stream(dist)
    dist.comm.ledger = CommLedger()
    run_program(dist, program, engine="scan", session=session)
    assert len(session.runners) == n_runners    # no new compilations
    assert _stream(dist) == first


def test_resolve_engine(monkeypatch):
    assert resolve_engine(None) == "scan"
    assert resolve_engine("python") == "python"
    monkeypatch.setenv("REPRO_ROUND_ENGINE", "python")
    assert resolve_engine("auto") == "python"
    with pytest.raises(ValueError):
        resolve_engine("jit")


def test_segment_validation():
    step = lambda dist, c, x: (c, c)
    with pytest.raises(ValueError):
        Segment(step, 0)
    with pytest.raises(ValueError):
        Segment(step, 3, xs=np.zeros(2))


# --------------------------------------------------------------------------
# runtime satellites
# --------------------------------------------------------------------------

def test_dot_rejects_wrong_rank():
    """A wrong-rank input used to silently reduce over the wrong axes."""
    bundle = _setup()
    dist = LocalDistERM(bundle.prob, bundle.part)
    w = dist.zeros_like_w()
    with pytest.raises(ValueError):
        dist.dot(w[None], w[None])          # (1, m, d_max)
    with pytest.raises(ValueError):
        dist.dot(w[0], w[0])                # (d_max,)
    with pytest.raises(ValueError):
        dist.dot(w, w[:, :-1])              # shape mismatch


def test_dot_masks_padding():
    """Values leaked into the pad region must not contribute."""
    prob = make_random_erm(n=8, d=10, loss="squared", lam=0.1, seed=0)
    part = even_partition(10, 3)            # ragged: blocks 4, 3, 3
    dist = LocalDistERM(prob, part)
    u = jnp.ones((part.m, part.d_max))      # garbage in the pad slots
    got = float(dist.dot(u, u))
    assert got == float(part.d)             # only the d valid coordinates


def test_loss_term_cache_within_round():
    """grad/hess evaluated once per (round, z); recomputed after
    end_round() and for a different z."""
    prob = make_random_erm(n=16, d=12, loss="logistic", lam=0.1, seed=3)
    part = even_partition(12, 3)
    dist = LocalDistERM(prob, part)
    calls = {"grad": 0, "hess": 0}
    base = prob.loss

    def counting(fn, key):
        def wrapped(z, y):
            calls[key] += 1
            return fn(z, y)
        return wrapped

    dist.loss = GLMLoss(name=base.name, value=base.value,
                        grad=counting(base.grad, "grad"),
                        hess=counting(base.hess, "hess"),
                        smoothness=base.smoothness)
    w = dist.scatter_w(jnp.linspace(-1, 1, 12))
    v = dist.scatter_w(jnp.linspace(1, -1, 12))
    z = dist.response(w)
    g1 = dist.pgrad(w, z)
    g2 = dist.pgrad(v, z)                   # same z: cached
    av = dist.response(v, tag="Av")
    h1 = dist.phvp(v, z, av)
    h2 = dist.phvp(w, z, av)                # same z: cached
    assert calls == {"grad": 1, "hess": 1}
    np.testing.assert_allclose(
        dist.gather_w(g1) - dist.gather_w(g2),
        prob.lam * (jnp.linspace(-1, 1, 12) - jnp.linspace(1, -1, 12)),
        atol=1e-6)
    dist.end_round()
    dist.pgrad(w, z)                        # new round: recomputed
    assert calls["grad"] == 2
    z2 = dist.response(v)
    dist.pgrad(w, z2)                       # different z: recomputed
    assert calls["grad"] == 3
    del h1, h2


def test_run_sharded_scan_requires_program():
    from repro.core.runtime import _run_sharded
    bundle = _setup()
    with pytest.raises(ValueError):
        _run_sharded(bundle.prob, lambda d_, r: None, rounds=2,
                     engine="scan")


# --------------------------------------------------------------------------
# sweep-level engine invariance (single small cell; the full matrix is in
# test_runtime_parity / test_ledger_invariance)
# --------------------------------------------------------------------------

def test_sweep_records_engine_invariant():
    from repro.experiments.sweep import SweepSpec, run_sweep

    spec = SweepSpec(
        name="engine-probe", instance="thm2_chain",
        grid=dict(d=[24], kappa=[16.0], lam=[0.5], m=[4]),
        algorithms=("dagd",), eps=(1e-3,), max_rounds=120)
    results = {eng: run_sweep(spec, engine=eng) for eng in ENGINES}
    base = [dataclasses.asdict(r) for r in results["python"].records]
    assert base and base[0]["measured_rounds"] is not None
    assert base[0]["certified"] is True
    for eng, result in results.items():
        got = [dataclasses.asdict(r) for r in result.records]
        for rec, ref in zip(got, base):
            rec, ref = dict(rec), dict(ref)
            assert rec.pop("engine") == eng
            ref.pop("engine")
            # the embedded RunSpec names the engine it ran under by
            # construction; everything else in it must agree
            assert rec.pop("run_spec")["engine"] == eng
            ref.pop("run_spec")
            assert rec == ref, (eng, rec, ref)


def test_hoisted_jit_passes_large_closures_as_arguments():
    """A compiled run must not embed the cell's data as constants (at
    deployment sizes that is gigabytes through lowering and compile):
    ``hoisted_jit`` feeds large closed-over arrays in as arguments, and
    still computes what ``jax.jit`` does."""
    import warnings

    import jax
    from repro.core.engine import HOIST_BYTES, hoisted_jit

    big = jnp.arange(HOIST_BYTES // 4 + 1, dtype=jnp.float32) / 7.0
    small = jnp.float32(3.0)

    def fn(x):
        return jnp.sum(big * x) + small

    before = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", HOIST_BYTES)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            got = hoisted_jit(fn)(jnp.float32(2.0))
        assert not [w for w in caught if "constants were captured"
                    in str(w.message)]
        with pytest.warns(UserWarning, match="constants were captured"):
            want = jax.jit(fn)(jnp.float32(2.0))
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", before)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
