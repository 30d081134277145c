#!/usr/bin/env python3
"""Prove the certifier's main path runs on a TPU, end to end.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the sharded solve only

One process drives everything through the entry points a user calls:

  (a) the ``thm2-small`` certification sweep, through the sweep CLI's
      ``main()``; every cell must certify with the committed round counts;
  (b) the whole-round kernel at its full tile (logistic, n=512, d=2048,
      m=4) under identity, bf16, int8 and a scheduled wire; a device trace
      must show the kernel running, and each run must match ``einsum``;
  (d) the certification service's ``--demo 96``, through its CLI
      ``main()``; every envelope ok, no fallback of any kind;
  (c) a dense logistic ERM at the shape of LIBSVM ``epsilon``
      (400,000 x 2,000, m=4, 3.2 GB of f32 on the device), checked
      against ``einsum``, with set-up time by phase, rounds/s over a warm
      window and peak device memory.

``--chips 4`` runs only phase (c)'s problem under ``placement="sharded"``
(machine j = chip j) and the same spec on one chip as its reference.

Each check prints one line.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}`` on success.  The script
exits non-zero, printing no result, when JAX finds no TPU; it exits
non-zero after ``{"ok": false, ...}`` when any check fails.  Outputs
(sweep reports, device traces) go to ``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import api  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.engine import EngineSession  # noqa: E402
from repro.kernels import fused_round  # noqa: E402
from repro.metrics import spans  # noqa: E402

OUT = ROOT / "chiprun_out" / "chip_smoke"

# (b): one A_j block of exactly one tile per machine; lam = 1e-5 makes
# kappa ~ 110, so eps = 1e-5 rel takes ~ 40 rounds, not 2
TILE = dict(n=512, d=2048, m=4, lam=1e-5, ref_iters=4000)
TILE_ROUNDS = 80
TILE_CHANNELS = ("identity", "bf16", "int8", "sched:int8@0,fp16@5")
# (c): LIBSVM epsilon's shape; lam ~ 4/n, the reference solve cut to
# what f32 resolves at this conditioning (kappa ~ 15)
EPSILON = dict(n=400_000, d=2_000, m=4, lam=1e-5, ref_iters=500)
EPSILON_ROUNDS = 300
EPS_REL = 1e-5
# The einsum reference runs at full f32 precision: by default XLA
# multiplies f32 matrices on the TPU in one bf16 pass.
REFERENCE_PRECISION = "highest"
# Bounds against that reference.  Each sits between the reading of the
# sound kernels and that of a control whose kernel dots run at Mosaic's
# default precision, one bf16 pass; both readings, from a TPU v5e, are
# in PERF.md.  max|w - w_einsum| / max|w_einsum| after PRECISION_ROUNDS
# rounds (sound <= 5.7e-7, control >= 1.0e-3):
PRECISION_ROUNDS = 2
PRECISION_RTOL = 1e-5
PRECISION_CHANNELS = ("identity", "bf16")
# and over the whole solve, max|w - w_einsum| / max|w_einsum| and
# max_k |gap_k - gap_einsum_k| / gap_0.  An int8 stage's dither hashes
# the value's bits, so one ulp moves a coordinate by a quantization
# step: on the int8 wire sound and control read alike, and its bounds
# only hold the solve near einsum's.
W_RTOL = {"identity": 1e-5, "bf16": 4e-4, "int8": 1e-2,
          "sched:int8@0,fp16@5": 1.5e-4, "epsilon": 1e-5}
GAP_RTOL = {"identity": 5e-7, "bf16": 2e-6, "int8": 1e-3,
            "sched:int8@0,fp16@5": 3e-5, "epsilon": 1e-6}
# The one cell whose committed count no longer holds: dgd at kappa = 64
# crosses eps = 1e-6 on the f32 noise floor of its gap, so the JAX
# release moves it (148 committed with jax 0.4.37, 145 on CPU and 146 on
# a TPU v5e with jax 0.9.0).  It is held to the v5e reading.
THM2_DRIFT = {("thm2_chain(d=96, kappa=64, lam=0.5, m=4, n=96)", "dgd"): 146}


class Checks:
    def __init__(self):
        self.failed = []

    def require(self, ok, what):
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _compiled():
    """(seconds, count) of every backend compile so far, as the
    program's compile counter (``repro.metrics.spans``) has them."""
    every = spans.snapshot()["compiles"].values()
    return (sum(c["seconds"] for c in every),
            sum(c["count"] for c in every))


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _memory(label):
    """Device 0's bytes in use and peak so far, after ``label``."""
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  memory after {label}: in use {stats.get('bytes_in_use')}, "
          f"peak {stats.get('peak_bytes_in_use')}", flush=True)


def _device_events(trace_dir: pathlib.Path):
    """Names of the device-side events in a profiler trace (an XLA op's
    event is named by its HLO text, ``%<instruction> = ...``)."""
    from jax.profiler import ProfileData
    for path in trace_dir.glob("plugins/profile/*/*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/device:TPU"):
                for line in plane.lines:
                    yield from (ev.name for ev in line.events)


def _gaps(pl, res):
    """The run's gap series, or its final gap where the placement has
    no in-run measurement (sharded)."""
    if res.gaps is not None:
        return np.asarray(res.gaps)
    b = pl.bundle
    with jax.default_matmul_precision(REFERENCE_PRECISION):
        return np.asarray([float(b.objective(jnp.asarray(res.w)))
                           - b.fstar])


def _reference(spec, bundle):
    """``spec`` run on the ``einsum`` backend at ``REFERENCE_PRECISION``."""
    with jax.default_matmul_precision(REFERENCE_PRECISION):
        return api.plan(spec.replace(backend="einsum"),
                        bundle=bundle).execute()


def _first_rounds(checks, label, spec, bundle):
    """``spec``'s iterate after ``PRECISION_ROUNDS`` rounds against the
    same rounds on ``einsum`` (local placement)."""
    short = spec.replace(rounds=PRECISION_ROUNDS, eps=(), measure="none")
    got = api.plan(short, bundle=bundle).execute()
    ref = _reference(short.replace(placement="local"), bundle)
    w, w_ref = np.asarray(got.w), np.asarray(ref.w)
    rel = float(np.max(np.abs(w - w_ref)) / np.max(np.abs(w_ref)))
    checks.require(rel <= PRECISION_RTOL,
                   f"{label}: after {PRECISION_ROUNDS} rounds max|w - "
                   f"w_einsum| / max|w_einsum| = {rel!r} <= "
                   f"{PRECISION_RTOL}")


def _compare(checks, label, pl, got, ref, tol):
    """The conformance contract of ``pl``'s run against the einsum
    reference; ``tol`` names the row of ``W_RTOL``/``GAP_RTOL``."""
    same = ((got.ledger.round_marks, got.ledger.typed_stream())
            == (ref.ledger.round_marks, ref.ledger.typed_stream()))
    checks.require(same, f"{label}: typed ledger stream and round marks "
                         f"identical to einsum "
                         f"({len(ref.ledger.records)} records)")
    if got.gaps is None:
        checks.require(got.rounds == ref.rounds,
                       f"{label}: rounds {got.rounds} == einsum "
                       f"{ref.rounds}")
    else:
        eps_abs = pl.eps_abs(EPS_REL)
        a, b = got.measured_rounds(eps_abs), ref.measured_rounds(eps_abs)
        checks.require(a is not None and b is not None and abs(a - b) <= 1,
                       f"{label}: measured rounds to {EPS_REL:g} rel {a} "
                       f"vs einsum {b} (within +-1)")
    w, w_ref = np.asarray(got.w), np.asarray(ref.w)
    rel = float(np.max(np.abs(w - w_ref)) / np.max(np.abs(w_ref)))
    checks.require(bool(np.all(np.isfinite(w))) and rel <= W_RTOL[tol],
                   f"{label}: max|w - w_einsum| / max|w_einsum| = {rel!r} "
                   f"<= {W_RTOL[tol]}")
    g, g_ref = _gaps(pl, got), _gaps(pl, ref)
    dev = float(np.max(np.abs(g - g_ref)) / pl.gap0())
    checks.require(dev <= GAP_RTOL[tol],
                   f"{label}: max|gap - gap_einsum| / gap_0 = {dev!r} <= "
                   f"{GAP_RTOL[tol]} over {len(g)} gaps (final gap "
                   f"{g[-1]!r}, einsum {g_ref[-1]!r})")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def _near(got, ref):
    return got is not None and ref is not None and abs(got - ref) <= 1


def phase_certify(checks):
    """(a) the thm2-small sweep through its CLI, against the committed
    round counts (``THM2_DRIFT`` names the one exception)."""
    from repro.experiments import sweep
    out = OUT / "sweep"
    rc = sweep.main(["--preset", "thm2-small", "--out", str(out)])
    checks.require(rc == 0, f"sweep exit code {rc}")
    key = lambda r: (r["instance_label"], r["algorithm"], r["eps"])
    committed = json.loads(
        (ROOT / "docs" / "results" / "thm2-small.json").read_text())
    want = {key(r): r["measured_rounds"] for r in committed["records"]}
    records = json.loads((out / "thm2-small.json").read_text())["records"]
    checks.require(len(records) == len(want),
                   f"{len(records)} records (committed {len(want)})")
    for r in records:
        got, ref = r["measured_rounds"], want.get(key(r))
        held = THM2_DRIFT.get((r["instance_label"], r["algorithm"]), ref)
        checks.require(
            r["certified"] is True and r["oracle_backend"] == "fused"
            and _near(got, held),
            f"{r['instance_label']} {r['algorithm']}: certified="
            f"{r['certified']} backend={r['oracle_backend']} measured "
            f"{got} (committed {ref}" + (f", held to {held}"
                                         if held != ref else "") + ")")


def phase_round_kernel(checks):
    """(b) the whole-round kernel at its full tile, per wire channel."""
    base = api.RunSpec(instance="logistic", instance_params=TILE,
                       algorithm="dagd", rounds=TILE_ROUNDS, eps=(EPS_REL,),
                       eps_mode="rel")
    bundle = api.plan(base).bundle
    checks.require(fused_round.round_step_fits(TILE["n"],
                                               bundle.part.d_max),
                   f"A_j block {TILE['n']} x {bundle.part.d_max} is "
                   f"admitted to the whole-round kernel")
    for ch in TILE_CHANNELS:
        spec = base.replace(channel=ch)
        pl = api.plan(spec, bundle=bundle)
        checks.require(pl.backend == "fused",
                       f"{ch}: auto resolved backend {pl.backend!r}")
        session = EngineSession()
        pl.execute(session)                            # compile
        trace = OUT / f"trace-{ch.replace(':', '_').replace(',', '_')}"
        shutil.rmtree(trace, ignore_errors=True)      # this run's trace only
        with jax.profiler.trace(str(trace)):
            res = pl.execute(session)
            jax.block_until_ready(res.w)
        names = list(_device_events(trace))
        ran = sum(n.lstrip("%").startswith(fused_round.KERNEL_NAME)
                  for n in names)
        if ran < res.rounds:
            print(f"  device events seen: {sorted(set(names))[:40]}")
        checks.require(ran >= res.rounds,
                       f"{ch}: device trace holds {ran} "
                       f"{fused_round.KERNEL_NAME} events for "
                       f"{res.rounds} rounds")
        ref = _reference(spec, bundle)
        _compare(checks, ch, pl, res, ref, ch)
        if ch in PRECISION_CHANNELS:
            _first_rounds(checks, ch, spec, bundle)


def phase_service(checks):
    """(d) the service's synthetic demo through its CLI."""
    from repro.serve.__main__ import main as serve_main
    stdout, stderr = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(stdout), \
            contextlib.redirect_stderr(stderr):
        rc = serve_main(["--demo", "96"])
    sys.stderr.write(stderr.getvalue())
    envelopes = [json.loads(line) for line in stdout.getvalue().splitlines()
                 if line.strip()]
    stats = next(json.loads(line[len("[serve] "):])
                 for line in stderr.getvalue().splitlines()
                 if line.startswith("[serve] "))
    print(f"  service stats: {json.dumps(stats)}")
    checks.require(rc == 0, f"serve exit code {rc}")
    checks.require(len(envelopes) == 96, f"{len(envelopes)} envelopes")
    bad = [e for e in envelopes if e["status"] != "ok"]
    checks.require(not bad, f"{len(bad)} envelopes not ok"
                   + (f" (first: {bad[0].get('error')})" if bad else ""))
    for key in ("group_failures", "engine_fallbacks", "dead_letters",
                "breaker_skips"):
        checks.require(stats[key] == 0, f"{key} = {stats[key]}")
    backends = {api.plan(api.RunSpec.from_dict(e["spec"])).backend
                for e in envelopes}
    checks.require(backends == {"fused"}, f"backends {sorted(backends)}")


def _build(spec):
    """Plan ``spec`` and build its instance, printing the build's parts
    and the compiles in each, from the program's spans."""
    before, t0 = spans.snapshot(), time.perf_counter()
    pl = api.plan(spec)
    bundle = pl.bundle
    total = time.perf_counter() - t0
    moved = spans.since(before)
    for name, part in moved["spans"].items():
        print(f"  set-up {name}: {part['total_s']:.2f} s "
              f"({part['count']} span(s), self {part['self_s']:.2f} s)")
    for name, c in moved["compiles"].items():
        print(f"  set-up compiles under {name or 'no span'}: {c['count']} "
              f"({c['seconds']:.2f} s, {c['cache_hits']} from the cache)")
    print(f"  set-up total {total:.2f} s; reference solve "
          f"ref_iters={spec.instance_params['ref_iters']}")
    _memory("instance build")
    return pl, bundle


def phase_epsilon(checks):
    """(c) the epsilon-shaped dense logistic ERM on one chip."""
    spec = api.RunSpec(instance="logistic", instance_params=EPSILON,
                       algorithm="dagd", rounds=EPSILON_ROUNDS,
                       eps=(EPS_REL,), eps_mode="rel")
    pl, bundle = _build(spec)
    checks.require(pl.backend == "fused",
                   f"auto resolved backend {pl.backend!r}")
    api.prepare_cell(pl)                 # A_stk, the per-machine blocks
    _memory("cell build")
    session = EngineSession()
    c0, t0 = _compiled(), time.perf_counter()
    res = pl.execute(session)
    jax.block_until_ready(res.w)
    c1 = _compiled()
    print(f"  cold run: {time.perf_counter() - t0:.2f} s, compile "
          f"{c1[0] - c0[0]:.2f} s in {c1[1] - c0[1]} compiles")
    _memory("cold run")
    c0, t0 = _compiled(), time.perf_counter()
    res = pl.execute(session)
    jax.block_until_ready(res.w)
    warm = time.perf_counter() - t0
    print(f"  warm window: {res.rounds} rounds in {warm:.3f} s = "
          f"{res.rounds / warm:.2f} rounds/s, "
          f"{_compiled()[1] - c0[1]} compiles inside")
    dev = jax.devices()[0]
    print(f"  peak_bytes_in_use: {_peak_bytes(dev)}")
    # the reference stacks its own copy of A: free this cell's first
    pl.release()
    del session
    _memory("release")
    ref = _reference(spec, bundle)
    _memory("einsum reference")
    _compare(checks, "epsilon fused", api.plan(spec, bundle=bundle), res,
             ref, "epsilon")
    _first_rounds(checks, "epsilon fused", spec, bundle)


def phase_sharded(checks):
    """The epsilon problem with machine j on chip j, against the same
    spec on one chip."""
    count = len(jax.devices())
    checks.require(count == EPSILON["m"],
                   f"{count} devices for m = {EPSILON['m']} machines")
    spec = api.RunSpec(instance="logistic", instance_params=EPSILON,
                       algorithm="dagd", rounds=EPSILON_ROUNDS,
                       placement="sharded", measure="none")
    pl, bundle = _build(spec)
    checks.require(pl.backend == "fused",
                   f"auto resolved backend {pl.backend!r}")
    for attempt in ("cold", "again"):
        c0, t0 = _compiled(), time.perf_counter()
        res = pl.execute()
        jax.block_until_ready(res.w)
        wall = time.perf_counter() - t0
        c1 = _compiled()
        print(f"  sharded run ({attempt}): {res.rounds} rounds in "
              f"{wall:.2f} s wall incl. trace, compile "
              f"{c1[0] - c0[0]:.2f} s in {c1[1] - c0[1]} compiles")
    for dev in jax.devices():
        print(f"  {dev}: peak_bytes_in_use {_peak_bytes(dev)} after the "
              f"sharded solve")
    # the same recipe on one chip: the sharded build carries its bits
    local = spec.replace(placement="local")
    ref = _reference(local, api.plan(local).bundle)
    _compare(checks, "sharded vs local einsum", pl, res, ref, "epsilon")
    for dev in jax.devices():
        print(f"  {dev}: peak_bytes_in_use {_peak_bytes(dev)} after the "
              f"one-chip reference")
    _first_rounds(checks, "sharded vs local einsum", spec, bundle)


def _run_phase(name, fn, checks):
    print(f"== phase {name}", flush=True)
    t0 = time.perf_counter()
    try:
        fn(checks)
    except Exception:   # report and go on: later phases still say things
        traceback.print_exc()
        checks.failed.append(f"phase {name} raised")
    print(f"== phase {name}: {time.perf_counter() - t0:.1f} s", flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the sharded four-chip path")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    OUT.mkdir(parents=True, exist_ok=True)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}, jax {jax.__version__}, compile cache "
          f"{cache}", flush=True)
    checks = Checks()
    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("a certify", phase_certify),
               ("b round kernel", phase_round_kernel),
               ("d service", phase_service),
               ("c epsilon", phase_epsilon)])
    for name, fn in phases:
        _run_phase(name, fn, checks)
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    if checks.failed:
        print(json.dumps(dict(ok=False, failed=checks.failed,
                              device=device)))
        return 1
    print(json.dumps(dict(ok=True, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
