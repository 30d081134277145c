"""Proximal accelerated gradient (FISTA) under the feature partition.

Composite objectives  f(w) + psi(w)  with coordinate-separable psi (L1,
box constraints, elastic net) fit the paper's communication model for
free: the prox operator acts coordinate-wise, so machine j applies
prox_{psi} to ITS OWN block with zero additional communication — the
round cost stays exactly one R^n ReduceAll, and the Theorem-2/3 lower
bounds (which hold for the smooth part) are still matched order-wise by
this algorithm. This extends the framework beyond the paper's smooth
setting at no communication cost.
"""
from __future__ import annotations

import math
from typing import Callable

import jax.numpy as jnp

from ..engine import RoundProgram, run_program
from ._fused import linear_round_program
from .dagd import fista_momentum_schedule


def soft_threshold(tau: float):
    """prox of tau*|w|_1 — elementwise, hence block-local."""
    def prox(w, step):
        t = tau * step
        return jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0)
    return prox


def box_projection(lo: float, hi: float):
    def prox(w, step):
        return jnp.clip(w, lo, hi)
    return prox


def prox_dagd_program(dist, rounds: int, L: float, prox: Callable,
                      lam: float = 0.0) -> RoundProgram:
    # The gradient-step scalar is f32-wrapped for const hoisting (see
    # dagd.py); the prox keeps receiving the python-float step size so a
    # prox that pre-multiplies it (soft_threshold's tau * step) rounds
    # exactly once, as it always has.
    inv_L = 1.0 / L
    step_L = jnp.float32(inv_L)

    if lam > 0:
        kappa = L / lam
        beta = jnp.float32((math.sqrt(kappa) - 1.0)
                           / (math.sqrt(kappa) + 1.0))

        def update(x, y, g, coeff):
            x_new = prox(y - step_L * g, inv_L)  # block-local prox
            y_new = x_new + beta * (x_new - x)
            return x_new, y_new

        return linear_round_program(dist, rounds, update, name="apg")

    def update(x, y, g, coeff):
        x_new = prox(y - step_L * g, inv_L)      # block-local prox
        y_new = x_new + coeff * (x_new - x)
        return x_new, y_new

    return linear_round_program(dist, rounds, update,
                                xs=fista_momentum_schedule(rounds),
                                name="fista")


def prox_dagd(dist, rounds: int, L: float, prox: Callable,
              lam: float = 0.0, history: bool = False,
              engine: str = "python"):
    """FISTA (lam=0) / accelerated proximal gradient (lam>0) on
    f(w) + psi(w); ``prox(w_block, step)`` must be coordinate-separable.
    One R^n ReduceAll per round, like DAGD."""
    res = run_program(dist,
                      prox_dagd_program(dist, rounds, L=L, prox=prox,
                                        lam=lam),
                      engine=engine, history=history)
    return (res.w, {"iterates": res.iterates}) if history else res.w
