"""CommLedger accounting + HLO collective audit."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.comm import (CommLedger, LocalCommunicator,
                             collective_bytes_from_hlo)


def test_ledger_accounting():
    led = CommLedger()
    comm = LocalCommunicator(4, led)
    x = jnp.ones((4, 100))          # 4 machines, R^100 each
    z = comm.reduce_all(x)
    assert z.shape == (100,)
    comm.end_round()
    assert led.rounds == 1
    assert led.total_bytes() == 100 * 4  # one R^100 f32 payload
    led.assert_budget(n=100, d=10)
    with pytest.raises(AssertionError):
        led.assert_budget(n=2, d=2, const=1)


def test_ledger_bytes_per_round():
    led = CommLedger()
    comm = LocalCommunicator(2, led)
    for _ in range(5):
        comm.reduce_all(jnp.ones((2, 50)))
        comm.end_round()
    assert led.bytes_per_round() == 50 * 4
    assert led.op_counts() == {"reduce_all": 5}


HLO_FIXTURE = """
HloModule test
ENTRY %main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}
  %ag = bf16[64,512]{1,0} all-gather(%x), replica_groups=[4,8]<=[32]
  %rs = f32[32,64]{1,0} reduce-scatter(%y), replica_groups={{0,1,2,3,4,5,6,7}}
  %cp = f32[16]{0} collective-permute(%z)
  %a2a = f32[8,8]{1,0} all-to-all(%w)
  %ars = f32[10]{0} all-reduce-start(%q)
  %ard = f32[10]{0} all-reduce-done(%ars)
}
"""


def test_collective_audit_fixture():
    audit = collective_bytes_from_hlo(HLO_FIXTURE)
    assert audit.count_by_op == {"all-reduce": 2, "all-gather": 1,
                                 "reduce-scatter": 1,
                                 "collective-permute": 1, "all-to-all": 1}
    assert audit.bytes_by_op["all-reduce"] == 128 * 256 * 4 + 10 * 4
    assert audit.bytes_by_op["all-gather"] == 64 * 512 * 2
    # reduce-scatter: result x group size (8)
    assert audit.bytes_by_op["reduce-scatter"] == 32 * 64 * 4 * 8
    assert audit.bytes_by_op["collective-permute"] == 16 * 4
    assert audit.bytes_by_op["all-to-all"] == 64 * 4


SHARDED_AUDIT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import CommLedger, make_random_erm
from repro.core.comm import collective_bytes_from_lowered
from repro.core.runtime import ShardedProgram
from repro.core.algorithms import PROGRAMS

out = {}

# (1) toy module: one all_gather, known payload
mesh = Mesh(np.array(jax.devices()), ("x",))
gather = jax.shard_map(lambda a: jax.lax.all_gather(a, "x"), mesh=mesh,
                   in_specs=P("x"), out_specs=P(None, "x"),
                   check_vma=False)
audit = collective_bytes_from_lowered(
    jax.jit(gather).lower(jnp.ones((4,), jnp.float32)))
out["toy"] = {"counts": audit.count_by_op, "bytes": audit.bytes_by_op}

# (2) the real sharded driver, lowered without running: the compiled
# module must carry every collective the trace-once ledger metered
prob = make_random_erm(n=16, d=8, loss="squared", lam=0.05, seed=1)
L = prob.smoothness_bound()
program = ShardedProgram(
    prob, 5, engine="scan",
    program_builder=lambda d_, r: PROGRAMS["dgd"](d_, r, L=L,
                                                  lam=prob.lam),
    channel="identity")
lowered, led = program.lower(), program.ledger
audit = collective_bytes_from_lowered(lowered)
out["dgd"] = {
    "counts": audit.count_by_op,
    "total_bytes": audit.total_bytes,
    "traced_records": len(led.records),
    "traced_bytes": sum(r.bytes for r in led.records),
}
print(json.dumps(out))
"""


def test_audit_on_real_module():
    """The parser finds the collectives of real lowered modules: a toy
    shard_map all_gather with a known payload, and the sharded driver's
    lowered ``ShardedProgram``, whose compiled HLO must carry at least the
    collective traffic the trace-once ledger metered."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", SHARDED_AUDIT_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    # toy: one all-gather of the full f32[2,2] result
    assert out["toy"]["counts"].get("all-gather") == 1
    assert out["toy"]["bytes"]["all-gather"] == 2 * 2 * 4

    # driver: dgd's per-round ReduceAll (psum of f32[16]) compiles to at
    # least one all-reduce; the scanned module carries the traced
    # payload at least once (scan traces each step exactly once)
    dgd = out["dgd"]
    assert dgd["counts"].get("all-reduce", 0) >= 1
    assert dgd["traced_records"] >= 1
    assert dgd["total_bytes"] >= dgd["traced_bytes"]
