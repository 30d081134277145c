"""The unified run API: RunSpec serialization, plan-time resolution and
validation, execution, and batched execution vs the sequential path.

The facade is the repo's single front door — every runnable surface
(sweep CLI, dryrun CLI, benchmarks, examples) constructs a RunSpec and
resolves ``auto`` choices through ``repro.api.plan``, so this suite pins
the contracts everything else leans on: JSON round-trips, eager
validation, env-var resolution at plan time, ledger identity between
sequential and batched execution, and re-execution of embedded specs.
"""
import numpy as np
import pytest

from repro import api
from repro.api import (ENGINES, ORACLE_BACKENDS, PLACEMENTS, PlanError,
                       RunSpec, execute_batch, plan, run)


TINY = dict(instance="thm2_chain",
            instance_params=dict(d=24, kappa=16.0, lam=0.5, m=4),
            algorithm="dagd", rounds=120, eps=(1e-3,))


# --------------------------------------------------------------------------
# RunSpec serialization
# --------------------------------------------------------------------------

def test_runspec_json_roundtrip():
    spec = RunSpec(**TINY, eps_mode="abs", backend="einsum", tag="probe")
    assert RunSpec.from_json(spec.to_json()) == spec
    assert RunSpec.from_dict(spec.to_dict()) == spec
    # numpy scalars from grid machinery are coerced to JSON types
    spec_np = RunSpec(**{**TINY, "instance_params":
                         dict(d=np.int64(24), kappa=np.float64(16.0),
                              lam=0.5, m=4)},
                      algo_kwargs=dict(L=np.float64(3.0),
                                       nested=[np.int32(1), 2]))
    assert spec_np.instance_params == TINY["instance_params"]
    assert spec_np.algo_kwargs == dict(L=3.0, nested=[1, 2])
    assert RunSpec.from_json(spec_np.to_json()) == spec_np


def test_runspec_rejects_unknown_fields_and_bad_enums():
    with pytest.raises(ValueError):
        RunSpec.from_dict(dict(TINY, bogus_field=1))
    with pytest.raises(ValueError):
        RunSpec(**TINY, eps_mode="relative")
    with pytest.raises(ValueError):
        RunSpec(**TINY, measure="maybe")


# --------------------------------------------------------------------------
# plan(): resolution + validation
# --------------------------------------------------------------------------

def test_plan_resolves_auto_axes_on_cpu(monkeypatch):
    monkeypatch.delenv(api.BACKEND_ENV, raising=False)
    monkeypatch.delenv(api.ENGINE_ENV, raising=False)
    monkeypatch.delenv(api.CHANNEL_ENV, raising=False)
    pl = plan(RunSpec(**TINY))
    assert (pl.placement, pl.backend, pl.engine, pl.channel) == \
        ("local", "einsum", "scan", "identity")
    assert pl.measure == "gap"          # auto: eps requested


def test_env_vars_read_at_plan_time(monkeypatch):
    monkeypatch.setenv(api.BACKEND_ENV, "kernel")
    monkeypatch.setenv(api.ENGINE_ENV, "python")
    pl = plan(RunSpec(**TINY))
    assert (pl.backend, pl.engine) == ("kernel", "python")
    monkeypatch.delenv(api.BACKEND_ENV)
    monkeypatch.delenv(api.ENGINE_ENV)
    pl = plan(RunSpec(**TINY))
    assert (pl.backend, pl.engine) == ("einsum", "scan")


def test_core_resolvers_delegate_to_api():
    """core.runtime/core.engine keep their historical names as shims over
    the single repro.api resolver; the mirrored axis lists must agree."""
    from repro.core import engine as core_engine
    from repro.core import runtime as core_runtime
    assert core_runtime.ORACLE_BACKENDS == ORACLE_BACKENDS
    assert core_engine.ENGINES == ENGINES
    assert core_runtime.resolve_oracle_backend("auto") == \
        api.resolve_oracle_backend("auto")
    assert core_engine.resolve_engine(None) == api.resolve_engine(None)
    assert set(PLACEMENTS) == {"local", "sharded"}


@pytest.mark.parametrize("bad, match", [
    (dict(TINY, instance="nope"), "unknown instance"),
    (dict(TINY, algorithm="nope"), "unknown algorithm"),
    (dict(TINY, instance_params=dict(zz=1)), "does not accept"),
    (dict(TINY, measure="none"), "measure='none'"),
    (dict(TINY, rounds=0), "rounds"),
    (dict(TINY, algorithm="bcd", placement="sharded", eps=(),
          measure="none"), "machine-stacked"),
    (dict(TINY, placement="sharded", engine="python"), "gap measurement"),
    (dict(TINY, algo_kwargs=dict(zz=1)), "hyper-parameter"),
    (dict(TINY, algo_kwargs=dict(rounds=5)), "hyper-parameter"),
    (dict(TINY, backend="blas"), "oracle backend"),
    (dict(TINY, channel="gzip"), "unknown channel"),
    (dict(TINY, instance=None), "BOTH instance and algorithm"),
])
def test_plan_rejects_invalid_specs(bad, match):
    with pytest.raises(PlanError, match=match):
        plan(RunSpec(**bad))


def test_plan_rejects_misaligned_bundle():
    """A pre-built bundle whose builder inputs differ from the spec's
    instance_params would execute a different problem than the embedded
    run_spec records — rejected on the stamped build_params."""
    from repro.experiments.instances import build_instance
    bundle = build_instance("thm2_chain", d=24, kappa=64.0, lam=0.5, m=4)
    with pytest.raises(PlanError, match="built with"):
        plan(RunSpec(**TINY), bundle=bundle)      # spec says kappa=16
    ok = build_instance("thm2_chain", **TINY["instance_params"])
    assert plan(RunSpec(**TINY), bundle=ok).bundle is ok


def test_resolution_only_plan():
    pl = plan(RunSpec(backend="einsum", engine="python"))
    assert pl.resolution_only
    assert (pl.backend, pl.engine) == ("einsum", "python")
    with pytest.raises(PlanError):
        pl.execute()


# --------------------------------------------------------------------------
# execution + re-execution from serialized specs
# --------------------------------------------------------------------------

def test_run_executes_and_reexecutes_verbatim():
    spec = RunSpec(**TINY)
    res = run(spec)
    assert res.rounds == res.ledger.rounds == spec.rounds
    assert res.gaps.shape == (spec.rounds,)
    assert res.budget_ok is True
    measured = res.measured_rounds(1e-3)
    assert measured is not None
    # the serialized spec re-executes to the identical measurement/meter
    res2 = run(RunSpec.from_json(spec.to_json()))
    assert res2.stream() == res.stream()
    assert res2.measured_rounds(1e-3) == measured
    np.testing.assert_array_equal(np.asarray(res2.w), np.asarray(res.w))


def test_plan_bound_matches_registry_theorem():
    pl = plan(RunSpec(**TINY))
    rep = pl.bound(1e-3)
    assert rep.theorem == "thm2"       # lam > 0, non-incremental
    assert rep.rounds > 0


def test_sharded_placement_matches_local():
    """placement='sharded' (1-device mesh on CPU) produces the same
    iterate and communication structure as the local reference."""
    base = dict(instance="random_ridge",
                instance_params=dict(n=16, d=12, m=1),
                algorithm="dagd", rounds=8, measure="none")
    loc = run(RunSpec(**base))
    sh = run(RunSpec(**base, placement="sharded"))
    np.testing.assert_allclose(np.asarray(sh.w), np.asarray(loc.w),
                               atol=1e-5, rtol=1e-5)
    assert sh.ledger.op_counts() == loc.ledger.op_counts()


# --------------------------------------------------------------------------
# execute_batch
# --------------------------------------------------------------------------

def _specs_grid():
    return [RunSpec(**{**TINY, "instance_params":
                       dict(d=24, kappa=k, lam=0.5, m=4),
                       "algorithm": a})
            for a in ("dagd", "dgd", "disco_f") for k in (16.0, 64.0)]


def test_execute_batch_groups_and_matches_sequential():
    specs = _specs_grid()
    seq = [plan(s).execute() for s in specs]
    bat = execute_batch([plan(s) for s in specs])
    assert all(r.batched for r in bat)   # every cell found a group
    for s, b in zip(seq, bat):
        assert b.stream() == s.stream()
        assert b.ledger.rounds == s.ledger.rounds
        assert b.measured_rounds(1e-3) == s.measured_rounds(1e-3)
        np.testing.assert_allclose(np.asarray(b.w), np.asarray(s.w),
                                   atol=1e-5, rtol=1e-5)


def test_execute_batch_falls_back_in_order():
    """Unbatchable plans (python engine, singleton shapes) still execute;
    results come back in input order."""
    specs = [RunSpec(**TINY),
             RunSpec(**TINY, engine="python"),
             RunSpec(**{**TINY, "rounds": 90}),       # singleton group
             RunSpec(**{**TINY, "instance_params":
                        dict(d=24, kappa=64.0, lam=0.5, m=4)})]
    results = execute_batch([plan(s) for s in specs])
    assert [r.spec for r in results] == specs
    assert results[1].batched is False and results[2].batched is False
    assert results[0].batched and results[3].batched   # group of two
    ref = plan(specs[1]).execute()
    assert results[1].stream() == ref.stream()


def test_sweep_batch_mode_matches_sequential():
    from repro.experiments.sweep import SweepSpec, run_sweep
    spec = SweepSpec(
        name="batch-probe", instance="thm2_chain",
        grid=dict(d=[24], kappa=[16.0, 64.0], lam=[0.5], m=[4]),
        algorithms=("dagd", "dgd"), eps=(1e-3,), max_rounds=120)
    seq = run_sweep(spec)
    bat = run_sweep(spec, execute="batch")
    assert [r.to_dict() for r in seq.records] == \
        [r.to_dict() for r in bat.records]
    assert seq.records[0].certified is True


def test_sweep_records_embed_reexecutable_spec():
    from repro.experiments.sweep import SweepSpec, run_sweep
    spec = SweepSpec(
        name="spec-probe", instance="thm2_chain",
        grid=dict(d=[16], kappa=[8.0], lam=[0.5], m=[2]),
        algorithms=("dagd",), eps=(1e-3,), max_rounds=100)
    rec = run_sweep(spec).records[0]
    assert rec.run_spec is not None
    res = run(RunSpec.from_dict(rec.run_spec))
    assert res.measured_rounds(rec.eps_abs) == rec.measured_rounds
    assert res.ledger.rounds == rec.ledger_rounds
    assert res.ledger.op_counts() == rec.op_counts


# --------------------------------------------------------------------------
# group_key composition (regression pin for the serving layer)
# --------------------------------------------------------------------------

def test_group_key_composition_partitions_the_axes():
    """Pin what ``Cell.group_key`` is made of.  The continuous-batching
    scheduler (``repro.serve``) pools submissions by this key, so a
    change in its composition silently changes which specs may share a
    compiled program: the leading components must stay
    (algorithm, backend, channel, rounds), placement/engine must never
    reach a key (unbatchable plans yield no cell), and a mixed batch
    must partition exactly as pinned here."""
    mixed = dict(
        k16=RunSpec(**TINY),
        k64=RunSpec(**{**TINY, "instance_params":
                       dict(d=24, kappa=64.0, lam=0.5, m=4)}),
        kernel=RunSpec(**TINY, backend="kernel"),
        fp16=RunSpec(**TINY, channel="fp16"),
        short=RunSpec(**{**TINY, "rounds": 90}),
        python=RunSpec(**TINY, engine="python"),
        sharded=RunSpec(instance="random_ridge",
                        instance_params=dict(n=16, d=12, m=1),
                        algorithm="dagd", rounds=8, measure="none",
                        placement="sharded"),
    )
    cells = {name: api.prepare_cell(plan(s)) for name, s in mixed.items()}

    # placement/engine never reach the pool: those plans are sequential
    assert cells["python"] is None and cells["sharded"] is None

    keys = {n: c.group_key() for n, c in cells.items() if c is not None}
    # same structure, different data -> same key (the whole point)
    assert keys["k16"] == keys["k64"]
    # each remaining axis, and the round budget, splits the key
    algo, backend, channel, rounds = keys["k16"][:4]
    assert (algo, backend, channel, rounds) == \
        ("dagd", "einsum", "identity", 120)
    assert keys["kernel"][:4] == ("dagd", "kernel", "identity", 120)
    assert keys["fp16"][:4] == ("dagd", "einsum", "fp16", 120)
    assert keys["short"][:4] == ("dagd", "einsum", "identity", 90)

    # the induced partition of the mixed batch, exactly
    groups = {}
    for name, cell in cells.items():
        if cell is not None:
            groups.setdefault(cell.group_key(), []).append(name)
    partition = sorted(sorted(g) for g in groups.values())
    assert partition == [["fp16"], ["k16", "k64"], ["kernel"], ["short"]]


# --------------------------------------------------------------------------
# adaptive channels: batching, grouping, and the frontier round trip
# --------------------------------------------------------------------------

def test_group_key_separates_schedules():
    """Scheduled channels reach the group key as their canonical wire
    channel: same schedule pools, different switch round never does, and
    a gap: spec pools with the sched: it resolves to."""
    k64 = {**TINY, "instance_params": dict(d=24, kappa=64.0, lam=0.5,
                                           m=4)}
    a = api.prepare_cell(plan(RunSpec(**TINY,
                                      channel="sched:int8@0,fp16@10")))
    b = api.prepare_cell(plan(RunSpec(**k64,
                                      channel="sched:int8@0,fp16@10")))
    c = api.prepare_cell(plan(RunSpec(**TINY,
                                      channel="sched:int8@0,fp16@20")))
    assert a.group_key() == b.group_key()
    assert a.group_key() != c.group_key()
    assert a.group_key()[2] == "sched:int8@0,fp16@10"


def test_execute_batch_matches_sequential_under_schedules():
    """The vmapped group threads the same global round indices the
    sequential scan does, so scheduled-channel ledgers — re-priced
    records, marks and all — stay bit-identical between the paths."""
    k64 = {**TINY, "instance_params": dict(d=24, kappa=64.0, lam=0.5,
                                           m=4)}
    specs = [RunSpec(**TINY, channel="sched:int8@0,fp16@10"),
             RunSpec(**k64, channel="sched:int8@0,fp16@10")]
    seq = [plan(s).execute() for s in specs]
    bat = execute_batch([plan(s) for s in specs])
    assert all(r.batched for r in bat)
    for s, b in zip(seq, bat):
        assert b.ledger.typed_stream() == s.ledger.typed_stream()
        assert b.ledger.round_marks == s.ledger.round_marks
        assert b.measured_rounds(1e-3) == s.measured_rounds(1e-3)
        np.testing.assert_allclose(np.asarray(b.w), np.asarray(s.w),
                                   atol=1e-5, rtol=1e-5)


def test_frontier_points_reexecute_bit_identically():
    """Differential gate for the bits-to-eps frontier: every point the
    search emits embeds a RunSpec, and re-executing that spec from its
    serialized form reproduces the verdicts, the measured rounds, and
    the total wire bits exactly — gap: points included (their schedule
    re-resolves from a fresh deterministic identity probe)."""
    from repro.experiments import frontier
    cell = dict(preset="thm2-small", instance="thm2_chain",
                instance_params=dict(d=24, kappa=16.0, lam=0.5, m=4),
                algorithm="dagd", rounds=120, eps=(1e-2, 1e-3),
                eps_mode="abs")
    record = frontier.run_cell(cell)
    assert any(p["adaptive"] for p in record["points"])
    assert any(p["channel"].startswith("gap:") for p in record["points"])
    for p in record["points"]:
        pl = plan(RunSpec.from_dict(p["run_spec"]))
        res = pl.execute()
        assert (res.wire_channel or res.channel) == p["wire_channel"]
        assert int(res.ledger.total_bits()) == p["total_bits"]
        for pe in p["per_eps"]:
            measured = res.measured_rounds(pl.eps_abs(pe["eps"]))
            assert measured == pe["measured_rounds"], p["channel"]
            assert pl.certify(res, pe["eps"]) == pe["certified"]
            if measured is not None:
                assert int(res.ledger.bits_through_round(measured)) == \
                    pe["bits_to_eps"], p["channel"]
        pl.release()
