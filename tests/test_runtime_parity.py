"""Backend conformance: {Local, Sharded} execution x {einsum, kernel,
fused} oracle backends x {python, scan} round engines must agree.

Run in a subprocess so the 8-device XLA flag doesn't leak into other
tests. Two layers:

  * ``test_shard_map_parity`` — the original Local-vs-shard_map parity on
    the default oracle backend.
  * ``test_backend_conformance_matrix`` — EVERY registered algorithm run
    under all eight (execution, oracle, engine) combinations produces
    matching final iterates and the same communication structure (the
    Local pairs additionally pin bit-identical ledger record streams
    across engines). Iterating the registry is deliberate: registering a
    new algorithm without a step-form program, or without teaching this
    suite how to drive it, fails the test.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.core import CommLedger, make_random_erm
from repro.core.partition import even_partition
from repro.core.runtime import LocalDistERM, _run_sharded
from repro.core.algorithms import dagd, dgd, disco_f

prob = make_random_erm(n=32, d=48, loss="squared", lam=0.05, seed=4)
L = prob.smoothness_bound()
part = even_partition(48, 8)
out = {}
for name, algo in [("dgd", dgd), ("dagd", dagd), ("disco_f", disco_f)]:
    w_sh, led = _run_sharded(prob, lambda d_, r: algo(d_, r, L=L,
                                                      lam=prob.lam),
                             rounds=25)
    dist = LocalDistERM(prob, part)
    w_lo = dist.gather_w(algo(dist, 25, L=L, lam=prob.lam))
    out[name] = {
        "max_diff": float(jnp.max(jnp.abs(w_sh - w_lo))),
        "sharded_ops": led.op_counts(),
        "local_ops": dist.comm.ledger.op_counts(),
    }
print(json.dumps(out))
"""


MATRIX_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
from repro.core import make_random_erm
from repro.core.engine import ENGINES, run_program
from repro.core.partition import even_partition
from repro.core.runtime import ORACLE_BACKENDS, LocalDistERM, _run_sharded
from repro.core.algorithms import ALGORITHMS, PROGRAMS
from repro.core.algorithms.prox_dagd import soft_threshold
from repro.experiments.registry import ALGORITHM_REGISTRY

M, D, N, R = 8, 48, 32, 12
prob = make_random_erm(n=N, d=D, loss="squared", lam=0.05, seed=4)
L = prob.smoothness_bound()
part = even_partition(D, M)
A = np.asarray(prob.A)
block_L = np.array(
    [np.linalg.norm(A[:, off:off + b], 2) ** 2 / N + prob.lam
     for off, b in zip(part.offsets, part.block_sizes)])
L_max = float(np.max(np.sum(A ** 2, axis=1)) + prob.lam)


def make_kwargs(name, sharded):
    # bcd needs its per-block constant in the stacked (m, 1) layout
    # locally vs a per-shard scalar under shard_map, so kwargs are built
    # lazily (axis_index only resolves inside the shard_map body)
    if name == "bcd":
        bl = jnp.asarray(block_L)
        if sharded:
            return lambda: dict(block_L=bl[lax.axis_index("model")], m=M)
        return lambda: dict(block_L=bl[:, None], m=M)
    if name == "dsvrg":
        return lambda: dict(L_max=L_max, lam=prob.lam, seed=7,
                            eta=1.0 / (4.0 * L_max))
    if name == "prox_dagd":
        return lambda: dict(L=L, lam=prob.lam, prox=soft_threshold(1e-3))
    return lambda: dict(L=L, lam=prob.lam)


def _stream(led):
    return [(r.kind, r.elems, r.bytes, r.tag) for r in led.records]


out = {}
for name in sorted(ALGORITHM_REGISTRY):
    iterates, op_counts, local_streams, scan_streams = {}, {}, {}, {}
    for be in ORACLE_BACKENDS:
        for eng in ENGINES:
            dist = LocalDistERM(prob, part, backend=be)
            program = PROGRAMS[name](dist, R, **make_kwargs(name, False)())
            res = run_program(dist, program, engine=eng)
            iterates[f"local/{be}/{eng}"] = dist.gather_w(res.w)
            op_counts[f"local/{be}/{eng}"] = dist.comm.ledger.op_counts()
            local_streams[f"local/{be}/{eng}"] = _stream(dist.comm.ledger)

            kw = make_kwargs(name, True)
            if eng == "python":
                w_sh, led = _run_sharded(
                    prob, lambda d_, r: ALGORITHMS[name](d_, r, **kw()),
                    rounds=R, backend=be)
            else:
                w_sh, led = _run_sharded(
                    prob, None, rounds=R, backend=be, engine="scan",
                    program_builder=lambda d_, r: PROGRAMS[name](d_, r,
                                                                 **kw()))
            iterates[f"sharded/{be}/{eng}"] = w_sh
            op_counts[f"sharded/{be}/{eng}"] = led.op_counts()
            if eng == "scan":
                scan_streams[f"sharded/{be}/{eng}"] = _stream(led)
    ref = iterates["local/einsum/python"]
    ref_ops = op_counts["local/einsum/python"]
    ref_stream = local_streams["local/einsum/python"]
    out[name] = {
        "combos": sorted(iterates),
        "max_diff": max(float(jnp.max(jnp.abs(w - ref)))
                        for w in iterates.values()),
        "ops_agree": all(ops == ref_ops for ops in op_counts.values()),
        "local_streams_identical": all(s == ref_stream
                                       for s in local_streams.values()),
        "sharded_scan_streams_identical": all(
            s == ref_stream for s in scan_streams.values()),
    }
print(json.dumps(out))
"""


def _run_script(script: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_shard_map_parity():
    out = _run_script(SCRIPT)
    for name, rec in out.items():
        assert rec["max_diff"] < 1e-4, (name, rec)
        # identical communication structure per round (trace-time count
        # for sharded == per-round python count for local)
        assert set(rec["sharded_ops"]) == set(rec["local_ops"]), name


@pytest.mark.slow
def test_backend_conformance_matrix():
    """Every registered algorithm x {Local, Sharded} x {einsum, kernel,
    fused} x {python, scan}: matching final iterates, identical per-run
    op counts, and bit-identical ledger record streams for every local
    run and every sharded scan run."""
    out = _run_script(MATRIX_SCRIPT)
    assert len(out) >= 6          # the six reference algorithms
    expected = sorted(f"{ex}/{be}/{eng}"
                      for ex in ("local", "sharded")
                      for be in ("einsum", "kernel", "fused")
                      for eng in ("python", "scan"))
    for name, rec in out.items():
        assert rec["combos"] == expected, name
        assert rec["max_diff"] < 1e-4, (name, rec)
        assert rec["ops_agree"], (name, rec)
        assert rec["local_streams_identical"], (name, rec)
        assert rec["sharded_scan_streams_identical"], (name, rec)
