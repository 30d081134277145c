"""Distributed gradient descent under the feature partition.

Round structure (exactly Definition 1's budget):
  computation phase : z = ReduceAll_j(A_j w_j)   (one R^n ReduceAll)
                      g_j = A_j^T l'(z)/n + lam w_j  (local)
  update            : w_j <- w_j - eta g_j            (local, own block only)

No communication-phase broadcast is ever needed: the iterate never has to
be materialized on one machine. This is the communication advantage the
paper attributes to partition-on-feature algorithms.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..engine import RoundProgram, run_program
from ._fused import linear_round_program


def dgd_program(dist, rounds: int, L: float, lam: float = 0.0
                ) -> RoundProgram:
    # f64-computed, f32-wrapped: same value the weak-typed float gave the
    # f32 update, but a hoistable const so repro.api.execute_batch can
    # group cells that differ only in L (see dagd.py).
    eta = jnp.float32(2.0 / (L + lam) if lam > 0 else 1.0 / L)

    def update(x, y, g, coeff):
        w_new = y - eta * g
        return w_new, w_new

    return linear_round_program(dist, rounds, update, name="gd")


def dgd(dist, rounds: int, L: float, lam: float = 0.0,
        history: bool = False, engine: str = "python"):
    """Plain GD with the standard step 2/(L+lam) (=1/L if lam=0)."""
    res = run_program(dist, dgd_program(dist, rounds, L=L, lam=lam),
                      engine=engine, history=history)
    return (res.w, {"iterates": res.iterates}) if history else res.w
