#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload epsilon-dagd --seed 7 --seconds 20 --trace 0

The cell (a workload of ``BENCHMARK.json``) names a configuration and a
traffic mix; both are files under ``bench/`` found by name.  One process
sets up (builds the instance, warms up every program the window runs),
measures for ``--seconds``, compares what the timed path produced with
the configuration's plain reference, and prints as the last line of
standard output one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a device trace of the window and reports the per-layer metrics,
``breakdown`` and the device's busy time.  ``checks`` holds each number
compared with its limit; they are also the last lines of standard error.

With no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from harness.cells import CellError, load_cell, peaks_for  # noqa: E402
from harness.state import CompileClock, Run  # noqa: E402

WORK = ".bench_work"              # traces, inside the checkout (gitignored)
CACHE = ".jax_cache"              # compiled programs, inside the checkout


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def _chip_devices(chips: int):
    """The accelerator's devices, or None where JAX finds no TPU or too
    few chips."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError:
        return None
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return devices


def main(argv=None, *, require_chip: bool = True, root=None) -> int:
    """``require_chip=False`` and ``root`` (a checkout holding other
    cells) are for the tests, which drive a run on the CPU at a small
    size."""
    args = _args(argv)
    try:
        cell = load_cell(args.workload, root)
    except (CellError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    root_dir = pathlib.Path(root) if root else HERE.parent
    if require_chip:
        # JAX's persistent compilation cache lives inside the checkout, at
        # one fixed path, whatever directory the environment names; the
        # program takes its directory from this variable
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root_dir / CACHE)
    import jax
    devices = _chip_devices(cell.chips)
    if devices is None and require_chip:
        print(f"bench: JAX found no TPU with {cell.chips} chip(s) (backend "
              f"{jax.default_backend()!r}); nothing was run", file=sys.stderr)
        return 3
    devices = devices or jax.devices()
    kind = devices[0].device_kind
    try:
        peaks = peaks_for(kind, root)
    except CellError as e:
        if require_chip:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        peaks = None
    if require_chip:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        # every program is cached, however short its compile: a later
        # run's set-up then compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver_name = cell.traffic["driver"]
    if not (HERE / "harness" / f"{driver_name}.py").is_file():
        print(f"bench: traffic {cell.traffic_name!r} names driver "
              f"{driver_name!r}, which bench/harness/ does not hold",
              file=sys.stderr)
        return 2
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), start=START,
              work_dir=root_dir / WORK / cell.name, clock=CompileClock(),
              peaks=peaks)
    import importlib
    driver = importlib.import_module(f"harness.{driver_name}")
    driver.run(run)
    print(json.dumps(_result(run, devices)))
    return 0


def _metric(m, value):
    return {"value": value, "unit": m["unit"]}


def _finite(value):
    """A number JSON can carry; a run that measured nothing reads null."""
    return value if value is not None and math.isfinite(value) else None


def _result(run: Run, devices) -> dict:
    for name, value in sorted(run.phases.items()):
        run.note(f"set-up phase {name}: {value:.3f} s")
    for part, where in (("setup", "in set-up"), ("window", "in the window")):
        c = run.counters
        run.note(f"compiles {where}: {c.get(f'compiles_{part}', 0)} "
                 f"({c.get(f'compile_s_{part}', 0.0):.3f} s), "
                 f"{c.get(f'cache_hits_{part}', 0)} of them found in the "
                 f"persistent cache")
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache and os.path.isdir(cache):
        run.note(f"compile cache {cache}: {len(os.listdir(cache))} entries, "
                 f"{'writable' if os.access(cache, os.W_OK) else 'READ-ONLY'}")
    run.note(f"memory_peak_bytes {run.memory_peak_bytes}")
    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {}
    if run.trace:
        from harness import trace
        covered = trace.busy_and_window(run)
        if covered is not None:
            device.update(busy_s=covered[0], window_s=covered[1])
            out["breakdown"] = trace.breakdown(run)
        for m in run.cell.per_layer:
            value = _finite(run.cell.reader(m["name"]).read(run))
            if value is not None:
                metrics[m["name"]] = _metric(m, value)
    else:
        for m in run.cell.end_to_end:
            value = _finite(run.end_to_end.get(m["name"]))
            if value is not None:
                metrics[m["name"]] = _metric(m, value)
    correct = bool(run.checks) and all(c.ok for c in run.checks)
    for c in run.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    result.update(out)
    result["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit}
                        for c in run.checks}
    return result


if __name__ == "__main__":
    sys.exit(main())
