"""Open-loop certification traffic through ``CertificationService``.

Traffic parameters (``bench/traffic/<t>.json``, ``"driver":
"serve_loop"``): ``rate`` (specs/s, Poisson), ``clients``,
``tail_percentile`` (of the latency the run reports), and optionally
``trace_tail_s``, the seconds at the window's end that a
``--trace 1`` run records (the TPU profiler keeps a fixed number of
trace buffers and drops the oldest device events of a longer
window; the host's counters cover the whole window).  The
configuration gives the spec pool (``instance``, ``instance_params``,
``kappa_range``, ``structures``, ``rounds``, ``eps``, ``eps_mode``) and
the service's settings (``service``).

Arrivals come from the seed, and every seed gets the same work in
another order.  The ``rate * seconds`` inter-arrival gaps are the
exponential distribution's quantiles in one fixed shuffled order (a
Poisson process's sample, the same for every seed: the order of the gaps
alone, which sets where the queue builds up, moved the latency tail by a
quarter between seeds); the kappas are the log-uniform quantiles over
``kappa_range`` and the structures come in equal shares, each list
shuffled by its own stream of the seed; clients take arrivals in turn.

The service is driven as a deployment drives it, on the real clock: at
each arrival ``step(now)`` then ``submit``; between arrivals ``step`` is
polled so that coalescing deadlines fire.  At the window's end what is
still pending is drained.  A spec's latency runs from when it was due to
when its envelope was released to its client; rejections and dead
letters count as failed.
"""
from __future__ import annotations

import contextlib
import math
import random
import resource
import time
from typing import List, NamedTuple

import numpy as np

from . import trace
from .state import Run, peak_bytes

# Limits of the comparison with the plain reference; both readings they
# were set from (a dozen seeds of sound runs, the control) are in PERF.md.
LIMITS = {
    "verdict_mismatch": 0,   # envelopes whose status, certified flag or
                             # bound differ from the reference's
    "stream_errors": 0,      # tickets lost, duplicated or released out of
                             # submission order within a client
    "ledger_mismatch": 0,    # typed ledger stream or round marks differ
                             # from the model's schedule
    "rounds_gap": 60,        # max |measured rounds - reference's|
    "w_rel": 2e-5,           # max|w - w_ref| / max|w_ref|, identity wire
}
POLL_S = 0.002               # longest sleep between polls of step()


class Arrival(NamedTuple):
    t: float                  # seconds after the window opens
    client: str
    kappa: float
    algorithm: str
    channel: str


def arrivals(seed: int, rate: float, seconds: float, clients: int,
             kappa_range, structures) -> List[Arrival]:
    """The arrival schedule of ``seed`` (see the module docstring)."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = list(-np.log1p(-q) / rate)
    lo, hi = (math.log(k) for k in kappa_range)
    kappas = list(np.exp(lo + q * (hi - lo)))
    kinds = [tuple(structures[i % len(structures)]) for i in range(n)]
    random.Random("gaps").shuffle(gaps)
    for stream, items in enumerate((kappas, kinds)):
        random.Random(f"{seed}/{stream}").shuffle(items)
    times = np.cumsum(gaps) - gaps[0]
    return [Arrival(float(t), f"c{i % clients}", float(k), a, c)
            for i, (t, k, (a, c)) in enumerate(zip(times, kappas, kinds))]


def _spec(cfg: dict, a: Arrival):
    from repro import api
    return api.RunSpec(instance=cfg["instance"],
                       instance_params=dict(cfg["instance_params"],
                                            kappa=a.kappa),
                       algorithm=a.algorithm, rounds=int(cfg["rounds"]),
                       eps=tuple(cfg["eps"]), eps_mode=cfg["eps_mode"],
                       channel=a.channel)


def percentile(values, p: float) -> float:
    """Nearest rank: the smallest value with at least p% of the values
    at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p / 100.0 * len(ordered)) - 1)]


def warm_up(run: Run, service, cfg: dict) -> None:
    """Every program the window can run: each structure at each batch
    width from 1 to ``max_batch`` (a drain of w pending specs of one
    structure is one batch of width w)."""
    lo, hi = cfg["kappa_range"]
    for width in range(1, int(cfg["service"]["max_batch"]) + 1):
        for algo, channel in cfg["structures"]:
            for j in range(width):
                kappa = lo * (hi / lo) ** ((j + 0.5) / width)
                service.submit(_spec(cfg, Arrival(0.0, "warm", kappa, algo,
                                                  channel)),
                               client_id="warm", now=time.monotonic())
        service.drain(time.monotonic())


class Window(NamedTuple):
    released: list        # (envelope, time released), in release order
    due: dict             # ticket -> (time due, Arrival)
    late: list            # seconds each arrival was submitted after due
    admit: list           # seconds inside each submit
    rejected: list        # (Arrival, error) refused at admission
    start: float
    end: float
    long_units: tuple = ()    # _Units.rows: the loop's units that took long
    host: dict = None         # what the process did over the window


class _Units:
    """Times each unit of the serve loop (a poll of ``step``, a sleep, a
    ``submit``, the drain) inside a ``bench.<kind>`` span, and keeps those
    that took ``LONG_S`` or more with what the host did meanwhile: the
    main thread's and the whole process's CPU seconds, their involuntary
    context switches, and how far the wall clock moved beyond the
    monotonic one.  A unit that took long with little CPU and many
    involuntary switches was preempted; one whose CPU was spent in
    another thread was held by it."""

    LONG_S = 0.5

    def __init__(self, start: float):
        self.start, self.rows = start, []

    @contextlib.contextmanager
    def unit(self, kind: str):
        from jax.profiler import TraceAnnotation
        before = _host_now()
        with TraceAnnotation("bench." + kind):
            yield
        after = _host_now()
        took = after["mono"] - before["mono"]
        if took >= self.LONG_S:
            d = {k: after[k] - before[k] for k in after}
            self.rows.append(dict(
                kind=kind, at=before["mono"] - self.start, took=took,
                wall_minus_mono=d["wall"] - d["mono"],
                thread_cpu=d["thread_cpu"], process_cpu=d["process_cpu"],
                thread_nivcsw=d["thread_nivcsw"],
                process_nivcsw=d["process_nivcsw"]))


def _host_now() -> dict:
    thread = resource.getrusage(resource.RUSAGE_THREAD)
    process = resource.getrusage(resource.RUSAGE_SELF)
    return dict(mono=time.monotonic(), wall=time.time(),
                thread_cpu=thread.ru_utime + thread.ru_stime,
                process_cpu=process.ru_utime + process.ru_stime,
                thread_nivcsw=thread.ru_nivcsw,
                process_nivcsw=process.ru_nivcsw)


def serve(service, cfg: dict, schedule, seconds: float,
          mark=lambda elapsed: None) -> Window:
    """Offer ``schedule`` to ``service`` on the real clock, keep polling
    until ``seconds`` have passed, then drain.  ``mark`` is called with
    the window's elapsed seconds before each submit."""
    released, due, late, admit, rejected = [], {}, [], [], []
    start = time.monotonic()
    units = _Units(start)
    host0 = _host_now()

    def step(now, drain=False):
        with units.unit("drain" if drain else "step"):
            out = service.drain(now) if drain else service.step(now)
        done = time.monotonic()
        released.extend((e, done) for e in out)

    for a in schedule:
        when = start + a.t
        while (now := time.monotonic()) < when:
            step(now)
            with units.unit("wait"):
                time.sleep(max(0.0, min(POLL_S, when - time.monotonic())))
        late.append(now - when)
        step(now)
        mark(time.monotonic() - start)
        t_sub = time.monotonic()
        try:
            with units.unit("submit"):
                ticket = service.submit(_spec(cfg, a), client_id=a.client,
                                        now=now)
            due[ticket] = (when, a)
        except (ValueError, RuntimeError) as e:
            rejected.append((a, e))
        admit.append(time.monotonic() - t_sub)
    while (now := time.monotonic()) < start + seconds:
        step(now)
        with units.unit("wait"):
            time.sleep(POLL_S)
    step(time.monotonic(), drain=True)
    host1 = _host_now()
    host = {k: host1[k] - host0[k] for k in host1}
    return Window(released, due, late, admit, rejected, start,
                  time.monotonic(), units.rows, host)


def summarize(w: Window, pct: float):
    """(released envelopes of the window's tickets with their release
    times, every such spec's latency from when it was due, the rate, the
    ``pct`` percentile of latency).  The percentile is taken over every
    spec of the window, late and drained ones too; ``specs_per_s`` counts
    the ok envelopes over the whole window, drain included."""
    released = [(e, t) for e, t in w.released if e.ticket in w.due]
    latencies = [t - w.due[e.ticket][0] for e, t in released]
    ok = sum(e.status == "ok" for e, _ in released)
    return (released, latencies, ok / (w.end - w.start),
            percentile(latencies, pct) if latencies else math.inf)


def run(run: Run) -> None:
    """Set up, warm up, serve the window, then compare every released
    envelope with the reference."""
    from repro.serve.service import CertificationService

    cfg, tr = run.cell.config, run.cell.traffic
    schedule = arrivals(run.seed, float(tr["rate"]), run.seconds,
                        int(tr["clients"]), cfg["kappa_range"],
                        cfg["structures"])
    t0 = time.monotonic()
    service = CertificationService(**cfg["service"])
    warm_up(run, service, cfg)
    run.phase("warm_up", t0)
    compiles = run.count_compiles("setup")
    warm_stats = service.stats()
    with trace.window(run, tr.get("trace_tail_s")) as mark:
        run.end_to_end["setup_s"] = time.monotonic() - run.start
        w = serve(service, cfg, schedule, run.seconds, mark)
    run.count_compiles("window", compiles)
    for a, e in w.rejected:
        run.note(f"arrival at {a.t:.3f} s rejected: {type(e).__name__}: {e}")

    stats = service.stats()
    pct = float(tr["tail_percentile"])
    released, latencies, rate, tail = summarize(w, pct)
    ok = [e for e, _ in released if e.status == "ok"]
    run.attempted = len(schedule)
    run.failed = len(w.rejected) + len(released) - len(ok)
    run.end_to_end["specs_per_s"] = rate
    batches = stats["batches"] - warm_stats["batches"]
    run.counters.update(
        {f"latency_p{pct:g}_s": tail},
        specs=len(w.due), released=len(latencies), window_s=w.end - w.start,
        admit_ms=1e3 * float(np.mean(w.admit)) if w.admit else None,
        batch_width=(sum(e.batched for e in ok) / batches if batches
                     else None))
    run.memory_peak_bytes = peak_bytes()
    if w.late:
        run.note(f"generator lateness p50 {percentile(w.late, 50):.6f} s, "
                 f"p95 {percentile(w.late, 95):.6f} s over {len(w.late)} "
                 f"arrivals")
    run.note(f"window: {len(w.due)} specs admitted, {len(latencies)} "
             f"released in {w.end - w.start:.3f} s; latency p50 "
             f"{percentile(latencies, 50) if latencies else math.nan:.4f} "
             f"s, p{pct:g} {tail:.4f} s; service {stats}")
    span = w.host["mono"]
    run.note(f"host over the window: process CPU {w.host['process_cpu']:.3f}"
             f" s, main thread CPU {w.host['thread_cpu']:.3f} s in "
             f"{span:.3f} s; involuntary switches {w.host['process_nivcsw']}"
             f" (main thread {w.host['thread_nivcsw']}); wall clock minus "
             f"monotonic {w.host['wall'] - span:+.6f} s; "
             f"{len(w.long_units)} loop units of {_Units.LONG_S} s or more")
    for row in sorted(w.long_units, key=lambda r: -r["took"])[:10]:
        run.note("long unit " + ", ".join(
            f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in row.items()))
    del service
    _compare(run, [e for e, _ in released], w.due)


def _stream_errors(released, due) -> int:
    """Tickets lost or duplicated, and envelopes released out of
    submission order within a client."""
    seen, errors, last = {}, 0, {}
    for e in released:
        if e.ticket not in due:
            errors += 1                  # not a ticket of this window
            continue
        seen[e.ticket] = seen.get(e.ticket, 0) + 1
        if e.seq <= last.get(e.client_id, -1):
            errors += 1
        last[e.client_id] = e.seq
    errors += sum(c - 1 for c in seen.values())
    errors += len(set(due) - set(seen))
    return errors


def _rounds_gap(a, b) -> int:
    """|a - b| of two measured round counts; eps reached on one side
    only reads as a gap of 10^9."""
    if a is None or b is None:
        return 0 if a == b else 10 ** 9
    return abs(a - b)


def readings(released, due, ref) -> dict:
    """The numbers compared, over every released envelope of the window;
    ``ref`` maps a ticket to the reference's judgement of its spec."""
    verdict, ledger, gap, w_rel = 0, 0, 0, 0.0
    for e in released:
        if e.ticket not in due:
            continue
        r = ref[e.ticket]
        v = e.verdicts[0] if e.status == "ok" and e.verdicts else None
        if (v is None or v["certified"] != r["certified"]
                or abs(v["bound_rounds"] - r["bound_rounds"])
                > 1e-6 * max(1.0, r["bound_rounds"])):
            verdict += 1
            continue
        gap = max(gap, _rounds_gap(v["measured_rounds"],
                                   r["measured_rounds"]))
        if e.result is not None:
            stream = e.result.ledger.typed_stream()
            marks = list(e.result.ledger.round_marks)
            if (stream, marks) != tuple(r["ledger"]):
                ledger += 1
            if e.spec.channel == "identity":
                w = np.asarray(e.result.w)
                w_rel = max(w_rel, float(np.max(np.abs(w - r["w"])))
                            / float(np.max(np.abs(r["w"]))))
    return dict(verdict_mismatch=verdict, ledger_mismatch=ledger,
                rounds_gap=gap, w_rel=w_rel)


def reference_judgements(cell, specs, precision: str):
    """The reference's judgement of each spec (kappa, algorithm,
    channel), with the model's ledger."""
    cfg = cell.config
    p = cfg["instance_params"]
    ref = cell.reference()
    out = ref.certify(specs, d=p["d"], lam=p["lam"], m=p["m"],
                      rounds=int(cfg["rounds"]), eps=float(cfg["eps"][0]),
                      precision=precision)
    for s, r in zip(specs, out):
        r["ledger"] = ref.expected_ledger(p["d"], int(cfg["rounds"]),
                                          s["channel"])
    return out


def control_readings(cell, seed: int, seconds: float) -> dict:
    """The control: the reference at the next precision below in the
    program's place, over the specs a window of ``seconds`` admits."""
    tr = cell.traffic
    schedule = arrivals(seed, float(tr["rate"]), seconds, int(tr["clients"]),
                        cell.config["kappa_range"], cell.config["structures"])
    specs = [dict(kappa=a.kappa, algorithm=a.algorithm, channel=a.channel)
             for a in schedule]
    sound = reference_judgements(cell, specs, cell.config["precision"])
    control = reference_judgements(cell, specs, "bf16_3x")
    verdict = sum(c["certified"] != s["certified"]
                  for c, s in zip(control, sound))
    gap = max(_rounds_gap(c["measured_rounds"], s["measured_rounds"])
              for c, s in zip(control, sound))
    w_rel = max(float(np.max(np.abs(c["w"] - s["w"])))
                / float(np.max(np.abs(s["w"])))
                for c, s, sp in zip(control, sound, specs)
                if sp["channel"] == "identity")
    return dict(verdict_mismatch=verdict, rounds_gap=gap, w_rel=w_rel)


def _compare(run: Run, released, due) -> None:
    t0 = time.monotonic()
    order = list(due)
    specs = [dict(kappa=due[t][1].kappa, algorithm=due[t][1].algorithm,
                  channel=due[t][1].channel) for t in order]
    judged = reference_judgements(run.cell, specs,
                                  run.cell.config["precision"]) if specs \
        else []
    ref = dict(zip(order, judged))
    got = readings(released, due, ref)
    got["stream_errors"] = _stream_errors(released, due)
    if not due:
        got["verdict_mismatch"] = 1
    run.note(f"reference: {len(specs)} specs judged in "
             f"{time.monotonic() - t0:.2f} s")
    for name in LIMITS:
        run.check(name, got[name], LIMITS[name])
