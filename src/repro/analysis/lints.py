"""Compile-hazard and determinism lints over traced step programs.

These are the failure modes that don't corrupt a single run but corrupt
*fleets* of runs:

* ``lint-rng`` — an RNG primitive inside a step jaxpr.  Stochastic
  choices must be pre-drawn into the scanned ``xs`` (as DSVRG's sampled
  row indices are): in-step RNG would make the trace-once schedule a
  sample rather than a certificate, and replaying the compiled step
  twice would disagree with the eager engine.
* ``lint-group-split`` — the same algorithm, traced on two instances
  that differ only in hyper-parameter *values*, must produce identical
  structure text; ``execute_batch`` groups on that text, so a baked-in
  python float silently splits what should be one compiled group into
  one compile per cell.  The diff names the first diverging jaxpr line.
* ``lint-weak-literal`` — weak-typed float literals in the structure
  (reported as context: each is a value that *would* split groups the
  moment it varies per cell; the algorithm builders wrap their hypers
  in ``jnp.float32`` to hoist them into consts for exactly this
  reason).
"""
from __future__ import annotations

from typing import List, Tuple

import jax.extend.core as jex

from .extract import TracedStep, format_eqn, iter_eqns
from .findings import Finding

_RNG_PRIMS = {
    "threefry2x32", "rng_bit_generator", "random_seed", "random_wrap",
    "random_bits", "random_fold_in", "random_split", "random_gamma",
}


def lint_rng(steps: List[TracedStep], algorithm: str = "",
             channel: str = "") -> List[Finding]:
    out: List[Finding] = []
    for ts in steps:
        for eqn, path in iter_eqns(ts.closed.jaxpr):
            if eqn.primitive.name in _RNG_PRIMS:
                out.append(Finding(
                    "lint-rng", "error",
                    f"RNG primitive '{eqn.primitive.name}' inside the "
                    f"step for segment(s) {ts.segments}; stochastic "
                    f"choices must be pre-drawn into the scanned xs so "
                    f"the traced schedule is a certificate, not a "
                    f"sample", eqn=format_eqn(eqn), path=path,
                    algorithm=algorithm, channel=channel))
    return out


def lint_weak_literals(steps: List[TracedStep], algorithm: str = "",
                       channel: str = "") -> List[Finding]:
    out: List[Finding] = []
    for ts in steps:
        seen = set()
        for eqn, path in iter_eqns(ts.closed.jaxpr):
            for v in eqn.invars:
                if not isinstance(v, jex.Literal):
                    continue
                aval = v.aval
                if getattr(aval, "weak_type", False) \
                        and getattr(aval, "dtype", None) is not None \
                        and aval.dtype.kind == "f":
                    key = (float(v.val), path)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(Finding(
                        "lint-weak-literal", "info",
                        f"weak-typed float literal {float(v.val)!r} "
                        f"baked into the structure of segment(s) "
                        f"{ts.segments}; if this value ever varies per "
                        f"cell it will split execute_batch groups",
                        eqn=format_eqn(eqn), path=path,
                        algorithm=algorithm, channel=channel))
    return out


def _first_diff(a: str, b: str) -> Tuple[int, str, str]:
    la, lb = a.splitlines(), b.splitlines()
    for i, (x, y) in enumerate(zip(la, lb)):
        if x != y:
            return i + 1, x.strip(), y.strip()
    return min(len(la), len(lb)) + 1, "<end>", "<end>"


def lint_group_stability(structures_a: List[str],
                         structures_b: List[str],
                         algorithm: str = "",
                         channel: str = "") -> List[Finding]:
    """Structure texts of the same algorithm traced under two
    hyper-parameter settings: any textual difference is a group split
    (the hyper leaked into the jaxpr instead of hoisting into a
    const)."""
    out: List[Finding] = []
    if len(structures_a) != len(structures_b):
        out.append(Finding(
            "lint-group-split", "error",
            f"hyper-parameter change altered the SEGMENT structure "
            f"({len(structures_a)} vs {len(structures_b)} distinct "
            f"steps)", algorithm=algorithm, channel=channel))
        return out
    for si, (sa, sb) in enumerate(zip(structures_a, structures_b)):
        if sa == sb:
            continue
        line, xa, xb = _first_diff(sa, sb)
        out.append(Finding(
            "lint-group-split", "error",
            f"step {si}: structure text diverges at jaxpr line {line} "
            f"under a pure hyper-parameter change — execute_batch "
            f"would compile this group once per cell.  "
            f"first diff: {xa!r} vs {xb!r}",
            algorithm=algorithm, channel=channel))
    return out


__all__ = ["lint_group_stability", "lint_rng", "lint_weak_literals"]
