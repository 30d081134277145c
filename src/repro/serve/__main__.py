"""CLI for the certification service.

    # synthetic heavy-traffic demo (seeded, deterministic trace)
    PYTHONPATH=src python -m repro.serve --demo 96

    # serve RunSpec JSONL from a file or stdin ("-"): one payload per
    # line, either a bare RunSpec object or {"client_id": ..., "spec": {...}}
    PYTHONPATH=src python -m repro.serve --input specs.jsonl

Envelopes stream to stdout as JSON lines as verdicts complete (per-
client submission order); rejected payloads become
``{"status": "rejected", ...}`` lines.  Service stats go to stderr, and
beside them the program's spans (``repro.metrics.spans``): where the
host time of admission, execution and release went, span by span, and
which span made each compile.
Exit status is non-zero iff any payload was rejected or any envelope
is a dead letter (``status="error"``).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Sequence

from ..compile_cache import enable_compile_cache
from ..metrics import spans
from .service import CertificationService
from .workload import Arrival, DEFAULT_STRUCTURES, synthetic_trace


def _read_arrivals(path: str, dt: float):
    fh = sys.stdin if path == "-" else open(path)
    arrivals = []
    try:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            client, payload = "anon", line
            try:
                doc = json.loads(line)
                if isinstance(doc, dict) and "spec" in doc:
                    client = str(doc.get("client_id", "anon"))
                    payload = doc["spec"]
                else:
                    payload = doc
            except json.JSONDecodeError:
                pass      # leave as raw text; admission reports it cleanly
            arrivals.append(Arrival(t=i * dt, client_id=client,
                                    spec=payload))
    finally:
        if fh is not sys.stdin:
            fh.close()
    return arrivals


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.serve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    src = parser.add_mutually_exclusive_group(required=True)
    src.add_argument("--demo", type=int, metavar="N",
                     help="serve a synthetic seeded trace of ~N specs")
    src.add_argument("--input", metavar="FILE",
                     help="RunSpec JSONL file ('-' for stdin)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--dt", type=float, default=1e-3,
                        help="trace inter-arrival time (injected clock)")
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--max-wait", type=float, default=0.05,
                        help="coalescing deadline on the injected clock")
    parser.add_argument("--cache-capacity", type=int, default=32)
    parser.add_argument("--max-depth", type=int, default=4096)
    args = parser.parse_args(argv)
    enable_compile_cache()

    if args.demo is not None:
        per = max(1, -(-args.demo // len(DEFAULT_STRUCTURES)))
        arrivals = synthetic_trace(n_per_structure=per, seed=args.seed,
                                   dt=args.dt)
    else:
        arrivals = _read_arrivals(args.input, args.dt)

    service = CertificationService(max_batch=args.max_batch,
                                   max_wait=args.max_wait,
                                   cache_capacity=args.cache_capacity,
                                   max_depth=args.max_depth)
    rejected = dead = 0

    def on_reject(arrival, err):
        nonlocal rejected
        rejected += 1
        print(json.dumps(dict(status="rejected",
                              client_id=arrival.client_id,
                              error=str(err))), flush=True)

    # Inline replay (rather than replay_trace) so envelopes stream to
    # stdout as their batches complete, not at end of trace.  Arrival
    # specs may be raw payloads (from --input); admission deserializes.
    def emit(envelopes):
        nonlocal dead
        for env in envelopes:
            dead += env.status != "ok"
            print(json.dumps(env.to_dict()), flush=True)

    last = 0.0
    for a in arrivals:
        emit(service.step(a.t))
        last = a.t
        try:
            service.submit(a.spec, client_id=a.client_id, now=a.t)
        except (ValueError, RuntimeError) as e:
            on_reject(a, e)
    emit(service.drain(last))

    print(f"[serve] {json.dumps(service.stats())}", file=sys.stderr)
    print(f"[spans] {json.dumps(spans.snapshot())}", file=sys.stderr)
    return 1 if rejected or dead else 0


if __name__ == "__main__":
    sys.exit(main())
