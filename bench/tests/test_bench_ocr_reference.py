"""The plain reference of ``ocr-logistic`` (PASCAL ``ocr`` on four
chips, ``bench/configs/ocr-logistic.*``) against the program, at a size
a test run holds: the configuration's recipe and lam at 8,192 x 1,024
over four forced host devices, A column-sharded.  These tests keep the
reference of the cell ``ocr-dagd-x4`` runnable and pin what it is read
with on the chip.  The mesh runs in a subprocess, so the device flag
does not leak into other tests."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, pathlib, sys
import numpy as np
import jax
from jax.sharding import Mesh
root = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(root / "bench"), str(root / "src")]
from harness import solve_loop
from harness.cells import load_json, load_module
from repro import api
from repro.core.erm import make_random_erm, random_erm_data

cfg = load_json(root / "bench" / "configs" / "ocr-logistic.json")
ref = load_module(root / "bench" / "configs" / "ocr-logistic.reference.py",
                  "reference_ocr")
N, D, ROUNDS = 8192, 1024, 300
p = dict(cfg["instance_params"], n=N, d=D)
seed = solve_loop.data_seed(2147483653)
out = {}

A, y = ref.make_data(seed, N, D)
mesh = Mesh(np.array(jax.devices()[:p["m"]]), ("model",))
A_p, y_p, _ = random_erm_data(N, D, loss="logistic", seed=seed, mesh=mesh)
out["bits"] = [bool(np.array_equal(np.asarray(A), np.asarray(A_p))),
               bool(np.array_equal(np.asarray(y), np.asarray(y_p)))]
del A_p, y_p
L = ref.smoothness(A, p["lam"])
out["L"] = [L, make_random_erm(N, D, loss="logistic", lam=p["lam"],
                               seed=seed, mesh=mesh).smoothness_bound()]
x_ref, _ = ref.solve(A, y, p["lam"], L, ROUNDS, precision="highest")
x_ctl, _ = ref.solve(A, y, p["lam"], L, ROUNDS, precision="bf16_3x")
del A, y

res = api.run(api.RunSpec(
    instance=cfg["instance"], instance_params=dict(p, seed=seed),
    algorithm="dagd", rounds=ROUNDS, eps=(1e-5,), eps_mode="rel",
    channel="identity", placement="sharded"))
scale = float(np.max(np.abs(x_ref)))
out["w_rel"] = float(np.max(np.abs(np.asarray(res.w) - x_ref))) / scale
out["control_w_rel"] = float(np.max(np.abs(x_ctl - x_ref))) / scale
stream, marks = ref.expected_ledger(N, ROUNDS)
out["ledger"] = [res.ledger.typed_stream() == stream,
                 list(res.ledger.round_marks) == marks]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_reference_data_is_the_programs(report):
    """The reference draws A and y column-sharded with the bits of the
    program's sharded build."""
    assert report["bits"] == [True, True]


def test_reference_smoothness_is_the_programs(report):
    ref, program = report["L"]
    assert abs(program - ref) <= 1e-6 * ref


def test_reference_ledger_model_is_the_programs(report):
    """One reduce_all of n float32 a round, marks 1..K: the typed stream
    and round marks of the program's sharded solve."""
    assert report["ledger"] == [True, True]


def test_program_agrees_and_control_does_not(report):
    """The sharded solve sits within the cell's ``w_rel`` limit of the
    reference; the reference at three bfloat16 passes does not."""
    from harness import solve_loop
    limit = solve_loop.LIMITS["w_rel"]
    assert report["w_rel"] <= limit, report
    assert report["control_w_rel"] > limit, report
