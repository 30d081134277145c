#!/usr/bin/env python3
"""Record the small trace that ``test_bench_program_trace.py`` reads:
the program's spans, compile markers and device scopes, on a TPU.

    python3 bench/tests/record_program_trace.py OUT_DIR

Inside one ``bench.window`` span, as a benchmark run records it: two
8-round dagd solves with the in-scan gap (logistic, n = 4,096,
d = 2,000, m = 4; its 4,096 x 500 blocks take the composed oracles, so
the padding to their block grid runs), then a certification service
that admits, runs and releases two Theorem 2 specs (d = 16, 30 rounds),
whose first admission compiles.  The solve is warmed up before the
trace.  The ``.xplane.pb`` lands under OUT_DIR, trimmed (``trim``) to
what the tests read; it is committed as
``bench/tests/data/small-program.xplane.pb``.
"""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

SOLVE = dict(instance="logistic",
             instance_params=dict(n=4096, d=2000, m=4, lam=1e-4,
                                  ref_iters=200),
             algorithm="dagd", rounds=8, eps=(1e-3,), eps_mode="rel")
SPECS = [dict(instance="thm2_chain",
              instance_params=dict(d=16, kappa=kappa, lam=0.5, m=4),
              algorithm="dagd", rounds=30, eps=(1e-6,))
         for kappa in (8.0, 32.0)]


def trim(data: bytes) -> bytes:
    """The trace without what no test reads: the HLO protos of the
    ``/host:metadata`` plane and the host threads that hold no
    ``bench.*`` or ``repro.*`` span (the runtime's own)."""
    from harness import program_trace
    space = program_trace.parse(data)
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    for plane in space.planes:
        if not plane.name.startswith("/host"):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        lines = [line for line in plane.lines if any(
            names.get(ev.metadata_id, "").startswith(("bench.", "repro."))
            for ev in line.events)]
        del plane.lines[:]
        plane.lines.extend(lines)
    return space.SerializeToString()


def main(out: str) -> int:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation
    from repro import api
    from repro.core.engine import EngineSession
    from repro.serve import CertificationService

    if jax.default_backend() != "tpu":
        print("record_program_trace: JAX found no TPU; nothing was "
              "recorded", file=sys.stderr)
        return 2
    pl = api.plan(api.RunSpec(**SOLVE))
    session = EngineSession()
    np.asarray(pl.execute(session).w)                 # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(2):
            with TraceAnnotation("bench.execute"):
                np.asarray(pl.execute(session).w)
        service = CertificationService(max_batch=2, max_wait=0.05)
        for spec in SPECS:
            service.submit(api.RunSpec(**spec), client_id="c", now=0.0)
        envelopes = service.drain(1.0)
    jax.profiler.stop_trace()
    if [e.status for e in envelopes] != ["ok", "ok"]:
        print(f"record_program_trace: envelopes {envelopes}", file=sys.stderr)
        return 1
    for path in pathlib.Path(out).glob("plugins/profile/*/*.xplane.pb"):
        path.write_bytes(trim(path.read_bytes()))
        print(f"{path} {path.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
