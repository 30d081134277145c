"""Algorithm registry: every optimizer self-describes its position in the
paper's taxonomy so the sweep runner can certify it automatically.

An ``AlgorithmSpec`` records what the theory needs to know:

  * ``family``       — "F^{lam,L}" (Definition 1's non-incremental family,
                       subject to Theorems 2/3) or "I^{lam,L}" (the
                       incremental family of Sec. 3.2, subject to Thm 4);
  * ``incremental``  — selects which lower bound certifies the algorithm;
  * ``accelerated``  — whether its known rate matches the bound order-wise
                       (the tightness witnesses: DAGD, DISCO-F);
  * ``make_kwargs``  — derives the algorithm's hyper-parameters from an
                       ``AlgoContext`` (smoothness constants, partition
                       shape, optional prox) so a sweep can run it on any
                       instance without per-algorithm glue;
  * ``program``      — the step-form registration: a
                       ``RoundProgram`` factory (``core.engine``) taking
                       the same kwargs as ``fn``, which is what the
                       scan-compiled round engine executes.  Registering
                       an algorithm without a step form is an error —
                       every sweep cell must be runnable under both
                       engines.

Registering a new algorithm here is all that is needed for it to appear in
every future sweep report with its measured rounds overlaid against the
correct theorem bound.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.core.algorithms import (bcd, bcd_program, dagd, dagd_program,
                                   dgd, dgd_program, disco_f,
                                   disco_f_program, dsvrg, dsvrg_program,
                                   prox_dagd, prox_dagd_program)

FAMILY_F = "F^{lam,L}"
FAMILY_I = "I^{lam,L}"


@dataclasses.dataclass(frozen=True)
class AlgoContext:
    """Everything an adapter may need to instantiate an algorithm on a
    concrete (problem, partition) pair. Built once per instance by
    ``instances.build_instance``.  ``L_max`` and ``block_L`` are each
    computed on first read, from the functions the builder gives: only
    ``dsvrg`` and ``bcd`` read them."""

    L: float                      # global smoothness bound of f
    lam: float                    # ridge / strong-convexity modulus
    m: int
    n: int
    d: int
    loss_name: str
    prox: Optional[Callable] = None   # separable prox for composite runs
    L_max_fn: Optional[Callable[[], float]] = None
    block_L_fn: Optional[Callable[[], np.ndarray]] = None

    @functools.cached_property
    def L_max(self) -> float:
        """Max per-component smoothness (Thm 4)."""
        return self.L_max_fn()

    @functools.cached_property
    def block_L(self) -> np.ndarray:
        """(m, 1) per-block Lipschitz bounds (BCD)."""
        return self.block_L_fn()


def _identity_prox(w, step):
    return w


@dataclasses.dataclass(frozen=True)
class AlgorithmSpec:
    name: str
    fn: Callable                  # fn(dist, rounds, history=True, **kwargs)
    family: str                   # FAMILY_F | FAMILY_I
    incremental: bool
    accelerated: bool
    description: str
    make_kwargs: Callable[[AlgoContext], dict]
    program: Callable             # program(dist, rounds, **kwargs)
                                  #   -> core.engine.RoundProgram
    local_only_kwargs: bool = False   # make_kwargs emits machine-stacked
                                      # arrays; repro.api.plan rejects
                                      # placement="sharded" for these

    @property
    def certifying_theorem(self) -> Tuple[str, str]:
        """(strongly-convex theorem, smooth-convex theorem) that lower-bound
        this algorithm's rounds. Incremental algorithms fall under Thm 4;
        everything in F^{lam,L} under Thm 2 (lam > 0) / Thm 3 (lam = 0)."""
        if self.incremental:
            return ("thm4", "thm4")
        return ("thm2", "thm3")


ALGORITHM_REGISTRY: Dict[str, AlgorithmSpec] = {}


def register_algorithm(spec: AlgorithmSpec) -> AlgorithmSpec:
    if spec.name in ALGORITHM_REGISTRY:
        raise ValueError(f"algorithm {spec.name!r} already registered")
    ALGORITHM_REGISTRY[spec.name] = spec
    return spec


def get_algorithm(name: str) -> AlgorithmSpec:
    try:
        return ALGORITHM_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown algorithm {name!r}; registered: "
            f"{sorted(ALGORITHM_REGISTRY)}") from None


# --------------------------------------------------------------------------
# The six reference algorithms
# --------------------------------------------------------------------------

register_algorithm(AlgorithmSpec(
    name="dgd", fn=dgd, program=dgd_program,
    family=FAMILY_F, incremental=False,
    accelerated=False,
    description="Distributed gradient descent; O(kappa log(1/eps)) — the "
                "unaccelerated baseline the bound separates from.",
    make_kwargs=lambda ctx: dict(L=ctx.L, lam=ctx.lam),
))

register_algorithm(AlgorithmSpec(
    name="dagd", fn=dagd, program=dagd_program,
    family=FAMILY_F, incremental=False,
    accelerated=True,
    description="Distributed Nesterov AGD; O(sqrt(kappa) log(1/eps)) — "
                "matches Theorem 2 (and Theorem 3 when lam = 0).",
    make_kwargs=lambda ctx: dict(L=ctx.L, lam=ctx.lam),
))

register_algorithm(AlgorithmSpec(
    name="prox_dagd", fn=prox_dagd, program=prox_dagd_program,
    family=FAMILY_F, incremental=False,
    accelerated=True,
    description="FISTA with a block-local separable prox; same one-"
                "ReduceAll round budget as DAGD (identity prox when the "
                "instance declares none).",
    make_kwargs=lambda ctx: dict(L=ctx.L, lam=ctx.lam,
                                 prox=ctx.prox or _identity_prox),
))

register_algorithm(AlgorithmSpec(
    name="bcd", fn=bcd, program=bcd_program,
    family=FAMILY_F, incremental=False,
    accelerated=False,
    description="Synchronous parallel block coordinate descent "
                "(Richtarik-Takac ESO step); practitioner's baseline.",
    make_kwargs=lambda ctx: dict(block_L=ctx.block_L, m=ctx.m),
    local_only_kwargs=True,       # block_L comes back stacked (m, 1)
))

register_algorithm(AlgorithmSpec(
    name="disco_f", fn=disco_f, program=disco_f_program,
    family=FAMILY_F, incremental=False,
    accelerated=True,
    description="DISCO-F damped Newton via distributed CG; matches "
                "Theorem 2 on quadratics (second-order information does "
                "not beat the bound).",
    make_kwargs=lambda ctx: dict(
        L=ctx.L, lam=ctx.lam,
        newton_steps=1 if ctx.loss_name == "squared" else 4),
))

register_algorithm(AlgorithmSpec(
    name="dsvrg", fn=dsvrg, program=dsvrg_program,
    family=FAMILY_I, incremental=True,
    accelerated=False,
    description="Feature-partitioned SVRG (incremental family); each "
                "stochastic step is one scalar-ReduceAll round. Tightness "
                "vs Theorem 4 is open.",
    make_kwargs=lambda ctx: dict(L_max=ctx.L_max, lam=ctx.lam, seed=7,
                                 eta=1.0 / (4.0 * ctx.L_max)),
))
