"""``python -m repro.analysis`` — audit the whole registry statically.

Traces every registered algorithm under every audited placement and
channel, proves the three static properties (schedule conformance,
algorithm-class certification, compile-hazard lints), runs the mutation
fixtures, and writes ``docs/results/static-audit.{json,md}``.  Exits
non-zero unless every cell verifies and every fixture is rejected —
the CI ``analysis`` leg gates on exactly this.

  python -m repro.analysis                 # full static audit + report
  python -m repro.analysis --execute       # + dynamic executed-run cross-check
  python -m repro.analysis --quick         # trimmed channel axis, no fixtures
  python -m repro.analysis --no-report     # verdict only, write nothing
"""
from __future__ import annotations

import argparse
import pathlib
import sys

from ..compile_cache import enable_compile_cache
from . import AUDIT_CHANNELS, AUDIT_PLACEMENTS, AUDIT_ROUNDS, \
    audit_registry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static audit of every registered algorithm's "
                    "communication schedule, class membership, and "
                    "compile hazards")
    ap.add_argument("--execute", action="store_true",
                    help="additionally cross-check each static schedule "
                         "against an executed run's ledger")
    ap.add_argument("--quick", action="store_true",
                    help="trim the channel axis and skip fixtures/"
                         "group-stability (fast sanity pass)")
    ap.add_argument("--rounds", type=int, default=AUDIT_ROUNDS,
                    help=f"round budget per audited cell "
                         f"(default {AUDIT_ROUNDS})")
    ap.add_argument("--channel", action="append", dest="channels",
                    metavar="NAME",
                    help="audit only this channel (repeatable; default: "
                         f"{', '.join(AUDIT_CHANNELS)})")
    ap.add_argument("--placement", action="append", dest="placements",
                    choices=list(AUDIT_PLACEMENTS),
                    help="audit only this placement (repeatable)")
    ap.add_argument("--out", type=pathlib.Path, default=None,
                    help="results directory (default docs/results)")
    ap.add_argument("--no-report", action="store_true",
                    help="print the verdict but write no files")
    args = ap.parse_args(argv)
    enable_compile_cache()

    report = audit_registry(
        channels=tuple(args.channels or AUDIT_CHANNELS),
        placements=tuple(args.placements or AUDIT_PLACEMENTS),
        rounds=args.rounds, execute=args.execute,
        fixtures=not args.quick, quick=args.quick)

    audited = [c for c in report.cells if not c.skipped]
    skipped = [c for c in report.cells if c.skipped]
    print(f"audited {len(audited)} cell(s) "
          f"({len(skipped)} skipped), "
          f"{len(report.fixtures)} fixture(s)")
    for f in report.errors():
        print(f"  ERROR {f}", file=sys.stderr)
    for fx in report.fixtures:
        if not fx.rejected:
            print(f"  ERROR fixture {fx.name!r} was NOT rejected "
                  f"(expected {fx.expect_codes})", file=sys.stderr)

    if not args.no_report:
        from ..experiments.report import default_results_dir, \
            refresh_index
        out = args.out or default_results_dir()
        out.mkdir(parents=True, exist_ok=True)
        (out / "static-audit.json").write_text(report.to_json() + "\n")
        (out / "static-audit.md").write_text(report.to_markdown())
        refresh_index(out)
        print(f"wrote {out / 'static-audit.json'}")
        print(f"wrote {out / 'static-audit.md'}")

    print(f"verdict: {'PASS' if report.ok else 'FAIL'}")
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
