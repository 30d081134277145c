"""Instances built column-sharded over a mesh, and the sharded solve.

An instance too large for one device is built with each machine's
columns of A on its own device (``make_random_erm(mesh=...)``), its
constants are computed where the blocks lie, and ``auto`` placement then
solves it under ``shard_map`` with the in-scan gap.  The mesh runs in a
subprocess on four forced host devices, so the flag does not leak into
other tests; each test reads one field of its JSON report.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.api import PlanError, RunSpec, _resolve, plan
from repro.experiments.instances import (INSTANCE_BUILDERS, INSTANCE_SHAPES,
                                         build_instance, instance_shape)

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from jax.sharding import Mesh
from jax._src import array as jarray
from repro import api
from repro.api import _resolve
from repro.core import erm
from repro.core.erm import random_erm_data, make_random_erm

N, D, M = 1024, 64, 4
mesh = Mesh(np.array(jax.devices()), ("model",))
out = {}

def same(a, b):
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))

for loss in ("logistic", "squared"):
    one = random_erm_data(N, D, loss=loss, seed=3)
    sh = random_erm_data(N, D, loss=loss, seed=3, mesh=mesh)
    out[f"bits_{loss}"] = [same(a, b) for a, b in zip(one, sh)]
    out[f"A_spec_{loss}"] = str(sh[0].sharding.spec)
    # z taken in several row chunks and a shorter last one
    rows, erm.CHUNK_ROWS = erm.CHUNK_ROWS, 384
    sh = random_erm_data(N, D, loss=loss, seed=3, mesh=mesh)
    erm.CHUNK_ROWS = rows
    out[f"bits_{loss}"] += [same(a, b) for a, b in zip(one, sh)]

one = make_random_erm(N, D, loss="logistic", seed=3)
sh = make_random_erm(N, D, loss="logistic", seed=3, mesh=mesh)
out["L"] = [one.smoothness_bound(), sh.smoothness_bound()]
del one, sh

# every fetch of a multi-device array to the host, by element count
fetched = []
value = jarray.ArrayImpl._value
jarray.ArrayImpl._value = property(
    lambda self: fetched.append(int(self.size)) or value.fget(self))

params = dict(n=N, d=D, m=M, lam=1e-3, seed=5, ref_iters=300)
base = dict(instance="logistic", instance_params=params, algorithm="dagd",
            rounds=40, eps=(1e-3,), eps_mode="rel")
_resolve.device_bytes_limit = lambda: 1000     # one device "too small"
pl = api.plan(api.RunSpec(**base))
out["placement"] = pl.placement
runs = [pl.execute(), pl.execute()]
out["largest_fetch"] = max(fetched, default=0)
out["live_A_sized"] = sorted({
    (str(x.sharding.spec) if hasattr(x.sharding, "spec") else "one device",
     len(x.sharding.device_set))
    for x in jax.live_arrays() if x.size >= N * D // M})
out["sharded_ctx"] = [float(pl.bundle.ctx.L_max),
                      pl.bundle.ctx.block_L.ravel().tolist()]
jarray.ArrayImpl._value = value

loc = api.plan(api.RunSpec(**base, placement="local"))
ref = loc.execute()
out["local_ctx"] = [float(loc.bundle.ctx.L_max),
                    loc.bundle.ctx.block_L.ravel().tolist()]
out["w_diff"] = max(float(np.max(np.abs(np.asarray(r.w) - np.asarray(ref.w))))
                    for r in runs)
out["w_scale"] = float(np.max(np.abs(np.asarray(ref.w))))
out["gap_diff"] = max(float(np.max(np.abs(r.gaps - ref.gaps))) for r in runs)
out["gap_shape"] = [list(r.gaps.shape) for r in runs]
out["stream_same"] = [r.ledger.typed_stream() == ref.ledger.typed_stream()
                      for r in runs]
out["marks_same"] = [list(r.ledger.round_marks) == list(ref.ledger.round_marks)
                     for r in runs]
out["marks"] = list(runs[0].ledger.round_marks)
out["records"] = [list(map(str, rec)) for rec in
                  sorted(set(runs[0].ledger.typed_stream()))]
out["measured"] = [runs[0].measured_rounds(pl.eps_abs(1e-3)),
                   ref.measured_rounds(loc.eps_abs(1e-3))]

# the audits read the module execute() runs, in-scan measure included
cell = pl.audit(execute=True)
out["audit"] = sorted({(f.code, f.severity) for f in cell.findings})
out["audit_executed"] = cell.executed
hlo = pl.audit_hlo_bytes()
program = pl._sharded_program(engine="scan")
program.lower()
out["hlo"] = dict(wire=hlo.wire_bytes, total=hlo.total_bytes,
                  measure=sum(hlo.measure_bytes_by_op.values()),
                  traced=sum(r.bytes for r in program.ledger.records))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("loss", ["logistic", "squared"])
def test_sharded_build_is_the_one_device_recipe(report, loss):
    """A, y and w_true built column-sharded carry the one-device
    recipe's bits."""
    assert report[f"bits_{loss}"] == [True] * 6
    assert report[f"A_spec_{loss}"] == "PartitionSpec(None, 'model')"


def test_sharded_smoothness_matches_host_svd(report):
    one, sharded = report["L"]
    assert abs(sharded - one) <= 1e-6 * one


def test_sharded_block_constants_match_local(report):
    (lmax_s, block_s), (lmax_l, block_l) = (report["sharded_ctx"],
                                            report["local_ctx"])
    assert lmax_s == pytest.approx(lmax_l, rel=1e-6)
    assert block_s == pytest.approx(block_l, rel=1e-5)


def test_auto_placement_shards_what_one_device_cannot_hold(report):
    assert report["placement"] == "sharded"


def test_sharded_build_and_solve_fetch_nothing_of_a_block(report):
    """No step of the sharded build, plan and solve brings A, or any
    array as large as one machine's block, to the host, and A is the
    only block-sized array left: column-sharded over the four devices."""
    assert report["largest_fetch"] < 1024 * 64 // 4
    assert report["live_A_sized"] == [["PartitionSpec(None, 'model')", 4]]


def test_sharded_solve_matches_local(report):
    assert report["w_diff"] <= 1e-5 * report["w_scale"]
    assert report["gap_shape"] == [[40], [40]]
    assert report["gap_diff"] <= 1e-6
    assert report["measured"][0] == report["measured"][1] is not None


def test_sharded_ledger_is_the_local_ledger(report):
    """One reduce_all of n float32 a round, round marks 1..K, on every
    run of the compiled program."""
    assert report["stream_same"] == [True, True]
    assert report["marks_same"] == [True, True]
    assert report["marks"] == list(range(1, 41))
    assert [r[:2] for r in report["records"]] == [["reduce_all", "1024"]]


def test_sharded_audits_read_the_executed_module(report):
    """The static audit and the collective-bytes audit certify the
    module ``execute()`` runs: the in-scan measure's psums are reported
    as measurement, not as unpriced communication, and are not counted
    toward the metered bytes."""
    assert ["class-measure", "info"] in report["audit"]
    assert not [f for f in report["audit"] if f[1] == "error"]
    assert report["audit_executed"]
    hlo = report["hlo"]
    assert hlo["measure"] >= 4 * 1024       # the objective's psum of z
    assert hlo["wire"] == hlo["total"] - hlo["measure"]
    assert hlo["wire"] >= hlo["traced"] > 0


@pytest.mark.parametrize("kind,params", [
    ("thm2_chain", dict(d=24, m=4)),
    ("thm3_chain", dict(d=24, m=4)),
    ("thm4_separable", dict(n=16, m=4)),
    ("lasso", dict(n=32, d=48, m=4, ref_iters=50)),
    ("logistic", dict(n=40, d=24, m=4, ref_iters=50)),
    ("random_ridge", dict(n=40, d=24, m=8)),
])
def test_instance_shape_is_the_built_shape(kind, params):
    """Each builder states the (n, d, m) it builds, for the placement
    choice made before anything is built."""
    assert set(INSTANCE_SHAPES) == set(INSTANCE_BUILDERS)
    b = build_instance(kind, **params)
    assert instance_shape(kind, params) == (b.prob.n, b.prob.d, b.part.m)
    assert instance_shape(kind, dict(params, bogus=1)) is None


def test_auto_placement_stays_local_where_one_device_holds_it(monkeypatch):
    monkeypatch.setattr(_resolve, "device_bytes_limit", lambda: 16 << 30)
    caps = dict(_resolve.capabilities(), devices=4)
    # epsilon's shape: 3.2 GB of A and 3.28 GB of kernel tiles
    assert _resolve.resolve_placement(
        "auto", shape=(400_000, 2_000, 4), caps=caps) == "local"
    assert _resolve.resolve_placement(
        "auto", shape=(3_500_000, 1_156, 4), caps=caps) == "sharded"
    # too few devices for the machines, or no limit reported: local
    assert _resolve.resolve_placement(
        "auto", shape=(3_500_000, 1_156, 8), caps=caps) == "local"
    monkeypatch.setattr(_resolve, "device_bytes_limit", lambda: None)
    assert _resolve.resolve_placement(
        "auto", shape=(3_500_000, 1_156, 4), caps=caps) == "local"
    # an explicit choice is kept
    assert _resolve.resolve_placement(
        "local", shape=(3_500_000, 1_156, 4), caps=caps) == "local"


def test_plan_resolves_existing_cells_local(monkeypatch):
    """The benchmark's one-chip cells keep the local placement on a
    four-chip host of 16 GB chips."""
    caps = dict(_resolve.capabilities(), devices=4)
    monkeypatch.setattr(_resolve, "capabilities", lambda: caps)
    monkeypatch.setattr(_resolve, "device_bytes_limit", lambda: 16e9)
    eps = plan(RunSpec(instance="logistic",
                       instance_params=dict(n=400_000, d=2_000, m=4,
                                            lam=1e-5, ref_iters=500),
                       algorithm="dagd", rounds=300, eps=(1e-5,),
                       eps_mode="rel"))
    chain = plan(RunSpec(instance="thm2_chain",
                         instance_params=dict(d=160, lam=0.5, m=4,
                                              kappa=64.0),
                         algorithm="dagd", rounds=3000, eps=(1e-6,)))
    assert eps.placement == chain.placement == "local"


def test_plan_accepts_eps_under_sharded():
    pl = plan(RunSpec(instance="thm2_chain",
                      instance_params=dict(d=24, kappa=16.0, lam=0.5, m=1),
                      algorithm="dagd", rounds=60, eps=(1e-6,),
                      placement="sharded"))
    assert (pl.placement, pl.measure) == ("sharded", "gap")
    res = pl.execute()
    assert res.gaps.shape == (60,)
    assert res.measured_rounds(1e-6) is not None
    with pytest.raises(PlanError, match="gap measurement"):
        plan(RunSpec(instance="thm2_chain",
                     instance_params=dict(d=24, kappa=16.0, lam=0.5, m=1),
                     algorithm="dagd", rounds=60, eps=(1e-6,),
                     placement="sharded", engine="python"))
