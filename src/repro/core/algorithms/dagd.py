"""Distributed accelerated gradient descent (Nesterov) — the matching
upper bound for Theorems 2 and 3.

Each round does exactly ONE ReduceAll of an R^n vector (z = A y); the
momentum extrapolation is block-local. Hence the algorithm sits inside
F^{lam,L} with the minimal possible communication, and its round count

   strongly convex : O( sqrt(kappa) log(1/eps) )   [Nesterov 2.2.22]
   smooth convex   : O( sqrt(L/eps) |w*| )         [Nesterov 2.2.19]

matches the paper's lower bounds — the tightness witnesses.

Expressed in step form (``dagd_program``) for the round engine: the FISTA
``t_k`` recursion is data-independent, so the smooth-case momentum
coefficients are precomputed per round in float64 and fed to the step as
the scanned ``xs`` — both engines then run bit-identical f32 arithmetic.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from ..engine import RoundProgram, run_program
from ._fused import linear_round_program


def fista_momentum_schedule(rounds: int) -> np.ndarray:
    """The (t_k - 1)/t_{k+1} coefficient sequence, rounded to f32 exactly
    as the historical Python loop's weak-typed scalars were."""
    t, coeffs = 1.0, []
    for _ in range(rounds):
        t_new = 0.5 * (1.0 + math.sqrt(1.0 + 4.0 * t * t))
        coeffs.append((t - 1.0) / t_new)
        t = t_new
    return np.asarray(coeffs, dtype=np.float32)


def dagd_program(dist, rounds: int, L: float, lam: float = 0.0
                 ) -> RoundProgram:
    # Scalar hypers are computed in f64 exactly as before, then wrapped as
    # f32 arrays: the step arithmetic sees the same f32 values the
    # weak-typed python floats produced, but the scalars become hoistable
    # jaxpr consts, so repro.api.execute_batch can group cells that differ
    # only in their hyper-parameters (a python-float literal would bake a
    # per-cell constant into the traced program).
    inv_L = jnp.float32(1.0 / L)

    if lam > 0:
        kappa = L / lam
        beta = jnp.float32((math.sqrt(kappa) - 1.0)
                           / (math.sqrt(kappa) + 1.0))

        def update(x, y, g, coeff):
            x_new = y - inv_L * g
            y_new = x_new + beta * (x_new - x)
            return x_new, y_new

        return linear_round_program(dist, rounds, update, name="agd")

    def update(x, y, g, coeff):
        x_new = y - inv_L * g
        y_new = x_new + coeff * (x_new - x)
        return x_new, y_new

    return linear_round_program(dist, rounds, update,
                                xs=fista_momentum_schedule(rounds),
                                name="fista")


def dagd(dist, rounds: int, L: float, lam: float = 0.0,
         history: bool = False, engine: str = "python"):
    res = run_program(dist, dagd_program(dist, rounds, L=L, lam=lam),
                      engine=engine, history=history)
    return (res.w, {"iterates": res.iterates}) if history else res.w
