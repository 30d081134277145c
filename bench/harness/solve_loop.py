"""Closed-loop solves: one client solving the configuration's problem
back to back, each solve waited for before the next starts.

Traffic parameters (``bench/traffic/<t>.json``, ``"driver":
"solve_loop"``): ``algorithm``, ``rounds`` per solve, ``eps`` and
``eps_mode`` (so the in-scan gap is measured, as a user solving to eps
runs it), ``channel``.

The window runs whole solves until ``--seconds`` have passed; the solve
under way then finishes, and ``rounds_per_s`` is every round of every
solve over the whole time from the window's start to the last solve's
end.  Every solve of the window is then compared with the configuration's
plain reference.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from . import trace
from .state import Run, peak_bytes

# Limits of the comparison with the plain reference.  Each sits between
# the largest reading of sound runs over a dozen seeds and the smallest
# reading of the control (the reference at the next precision below);
# both readings, from a TPU v5e, are in PERF.md.
LIMITS = {
    "ledger_mismatch": 0,       # solves whose typed ledger stream or round
                                # marks differ from the model's schedule
    "w_rel": 3e-6,              # max|w - w_ref| / max|w_ref|, worst solve
}
# Printed, not compared: the in-scan objective series against the
# reference's, max_k |f_k - f_ref_k| / (f_ref(0) - min f_ref).  The
# objective is a mean over every row, so a lower precision moves it less
# than float32 resolves: sound runs and the control read alike (PERF.md).
DIAGNOSTIC = ("f_dev",)


def data_seed(seed: int) -> int:
    """The program's data seed for the benchmark's ``--seed`` (JAX's
    PRNG takes a 31-bit key)."""
    return int(seed) % (2 ** 31 - 1)


def make_spec(run: Run):
    from repro import api
    cfg, tr = run.cell.config, run.cell.traffic
    params = dict(cfg["instance_params"], seed=data_seed(run.seed))
    return api.RunSpec(instance=cfg["instance"], instance_params=params,
                       algorithm=tr["algorithm"], rounds=int(tr["rounds"]),
                       eps=tuple(tr["eps"]), eps_mode=tr["eps_mode"],
                       channel=tr["channel"])


def run(run: Run) -> None:
    """Set up, warm up, measure, then compare with the reference."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro import api
    from repro.core.engine import EngineSession

    spec = make_spec(run)
    t0 = time.monotonic()
    pl = api.plan(spec)
    run.phase("plan", t0)
    t0 = time.monotonic()
    bundle = pl.bundle
    jax.block_until_ready(bundle.prob.A)
    run.phase("instance_build", t0)
    n = bundle.prob.n
    fstar = float(bundle.fstar)
    del bundle
    session = EngineSession()
    t0 = time.monotonic()
    warm = pl.execute(session)                     # builds the cell, compiles
    jax.block_until_ready(warm.w)
    run.phase("warm_up_solve", t0)
    del warm
    compiles = run.count_compiles("setup")

    solves, res = [], None
    with trace.window(run):
        start = time.monotonic()
        run.end_to_end["setup_s"] = start - run.start
        while True:
            run.attempted += 1
            try:
                with TraceAnnotation("bench.execute"):
                    res = pl.execute(session)
                with TraceAnnotation("bench.result"):
                    w = np.asarray(res.w)
            except Exception as e:      # a failed solve ends the window
                run.failed += 1
                run.note(f"solve {run.attempted} raised "
                         f"{type(e).__name__}: {e}")
                break
            solves.append((w, np.asarray(res.gaps, dtype=np.float64),
                           res.ledger.typed_stream(),
                           list(res.ledger.round_marks), res.rounds))
            if time.monotonic() - start >= run.seconds:
                break
        end = time.monotonic()
    rounds = sum(s[4] for s in solves)
    run.end_to_end["rounds_per_s"] = rounds / (end - start)
    run.counters.update(solves=len(solves), rounds=rounds,
                        window_s=end - start)
    run.count_compiles("window", compiles)
    run.memory_peak_bytes = peak_bytes()
    run.note(f"window: {len(solves)} solves, {rounds} rounds in "
             f"{end - start:.3f} s")

    # the reference holds its own copy of the data: free the program's
    pl.release()
    del pl, session, res
    gc.collect()
    _compare(run, solves, fstar, n)


def reference_solves(cell, seed: int, precisions):
    """The reference's solve of ``cell`` for ``seed`` at each of
    ``precisions``: {precision: (w, objective per round)}."""
    ref = cell.reference()
    p, rounds = cell.config["instance_params"], int(cell.traffic["rounds"])
    A, y = ref.make_data(data_seed(seed), p["n"], p["d"])
    L = ref.smoothness(A, p["lam"])
    out = {prec: ref.solve(A, y, p["lam"], L, rounds, precision=prec)
           for prec in precisions}
    del A, y
    return out


def readings(solves, x_ref, f_ref, want) -> dict:
    """The numbers compared, worst over ``solves``, each a tuple (w,
    objective per round, typed ledger stream, round marks); ``want`` is
    the model's (stream, marks)."""
    if not solves:
        return dict(ledger_mismatch=1, w_rel=math.inf, f_dev=math.inf)
    gap0 = math.log(2.0) - float(np.min(f_ref))
    scale = float(np.max(np.abs(x_ref)))
    return dict(
        ledger_mismatch=sum((s[2], s[3]) != tuple(want) for s in solves),
        w_rel=max(float(np.max(np.abs(s[0] - x_ref))) / scale
                  for s in solves),
        f_dev=max(float(np.max(np.abs(s[1] - f_ref))) / gap0
                  for s in solves))


def control_readings(cell, seed: int) -> dict:
    """The control: the reference at the next precision below, put in
    the program's place and compared with the reference as the program
    is."""
    got = reference_solves(cell, seed, (cell.config["precision"],
                                        "bf16_3x"))
    (x_ref, f_ref), (x_c, f_c) = got[cell.config["precision"]], got["bf16_3x"]
    want = cell.reference().expected_ledger(
        cell.config["instance_params"]["n"], int(cell.traffic["rounds"]))
    return readings([(x_c, f_c) + tuple(want)], x_ref, f_ref, want)


def _compare(run: Run, solves, fstar: float, n: int) -> None:
    """Every solve of the window against one reference solve (each
    solve of the window solves the same problem from the same start).
    The program's gap series plus its f* is its objective per round."""
    t0 = time.monotonic()
    prec = run.cell.config["precision"]
    x_ref, f_ref = reference_solves(run.cell, run.seed, (prec,))[prec]
    want = run.cell.reference().expected_ledger(
        n, int(run.cell.traffic["rounds"]))
    got = readings([(w, gaps + fstar, stream, marks)
                    for w, gaps, stream, marks, _ in solves],
                   x_ref, f_ref, want)
    run.note(f"reference solve {time.monotonic() - t0:.2f} s "
             f"({len(solves)} solves compared)")
    for name in DIAGNOSTIC:
        run.note(f"{name} {got[name]!r} (not compared)")
    for name in LIMITS:
        run.check(name, got[name], LIMITS[name])
