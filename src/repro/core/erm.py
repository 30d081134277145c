"""Empirical risk minimization objectives in the feature-partitioned model.

The paper's ERM form (Eq. 1):  f(w) = (1/n) sum_i phi(w, A_i:) [+ lam/2 |w|^2]

The key structural fact the whole paper leans on: for GLM-type losses
(squared, logistic, squared hinge) every machine can compute its partial
gradient

    f'_j(w) = (1/n) A_j^T ell'(z) + lam w_j,      z = A w = sum_j A_j w_j

from the *shared* R^n vector z, and z is exactly ONE ReduceAll of an R^n
vector per round (each machine contributes its local z_j = A_j w_j).
Similarly Hessian-vector products (f''(w) v)^[j] = (1/n) A_j^T (ell''(z) *
(A v)) + lam v_j need the same single ReduceAll — this is what makes
DISCO-F communication-cheap on these losses.

Losses are expressed by per-sample scalar functions of the margin/response
so the same machinery serves all of them.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np
import scipy.linalg
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GLMLoss:
    """A GLM loss  (1/n) sum_i ell(z_i, y_i) + lam/2 |w|^2,  z = A w."""

    name: str
    value: Callable  # (z, y) -> per-sample loss vector
    grad: Callable   # (z, y) -> d ell / d z          (R^n)
    hess: Callable   # (z, y) -> d^2 ell / d z^2      (R^n, diagonal)
    smoothness: float  # max of ell'' (per-sample curvature bound)

    def full_value(self, z, y, w, lam):
        n = z.shape[0]
        return jnp.sum(self.value(z, y)) / n + 0.5 * lam * jnp.vdot(w, w)


def squared_loss() -> GLMLoss:
    return GLMLoss(
        name="squared",
        value=lambda z, y: 0.5 * (z - y) ** 2,
        grad=lambda z, y: z - y,
        hess=lambda z, y: jnp.ones_like(z),
        smoothness=1.0,
    )


def logistic_loss() -> GLMLoss:
    # y in {-1, +1}; ell = log(1 + exp(-y z))
    def _val(z, y):
        return jnp.logaddexp(0.0, -y * z)

    def _grad(z, y):
        return -y * jax.nn.sigmoid(-y * z)

    def _hess(z, y):
        s = jax.nn.sigmoid(-y * z)
        return s * (1.0 - s)

    return GLMLoss("logistic", _val, _grad, _hess, smoothness=0.25)


def squared_hinge_loss() -> GLMLoss:
    # y in {-1, +1}; ell = max(0, 1 - y z)^2 / 2
    def _val(z, y):
        return 0.5 * jnp.maximum(0.0, 1.0 - y * z) ** 2

    def _grad(z, y):
        return -y * jnp.maximum(0.0, 1.0 - y * z)

    def _hess(z, y):
        return (1.0 - y * z > 0).astype(z.dtype)

    return GLMLoss("squared_hinge", _val, _grad, _hess, smoothness=1.0)


LOSSES = {
    "squared": squared_loss,
    "logistic": logistic_loss,
    "squared_hinge": squared_hinge_loss,
}


@dataclasses.dataclass(frozen=True)
class ERMProblem:
    """A concrete ERM instance: data (A, y), loss, ridge lam."""

    A: jnp.ndarray           # (n, d)
    y: jnp.ndarray           # (n,)
    loss: GLMLoss
    lam: float = 0.0

    @property
    def n(self) -> int:
        return self.A.shape[0]

    @property
    def d(self) -> int:
        return self.A.shape[1]

    # ---- whole-vector oracle (reference; no partitioning) --------------
    def value(self, w) -> jnp.ndarray:
        z = self.A @ w
        return self.loss.full_value(z, self.y, w, self.lam)

    def gradient(self, w) -> jnp.ndarray:
        z = self.A @ w
        return self.A.T @ self.loss.grad(z, self.y) / self.n + self.lam * w

    def hvp(self, w, v) -> jnp.ndarray:
        """Hessian-vector product at w."""
        z = self.A @ w
        h = self.loss.hess(z, self.y)
        return self.A.T @ (h * (self.A @ v)) / self.n + self.lam * v

    def smoothness_bound(self) -> float:
        """L <= ell''_max * sigma_max(A)^2 / n + lam.

        One scalar of set-up, taken by the host's LAPACK on every
        platform: the TPU's SVD takes minutes to compile at d in the
        thousands, LAPACK seconds to run.  SciPy's ``gesdd`` is the routine
        JAX's CPU SVD calls, so this equals ``jnp.linalg.norm(A, ord=2)``
        on the CPU bit for bit."""
        smax = scipy.linalg.svdvals(np.asarray(self.A))[0]
        return float(self.loss.smoothness * smax ** 2 / self.n + self.lam)

    # ---- feature-partitioned oracles (machine-local pieces) ------------
    # These are the per-machine computations; the single ReduceAll that
    # forms z (or Av) is done by the caller (runtime / shard_map body).
    def local_response(self, A_j, w_j) -> jnp.ndarray:
        """z_j = A_j w_j  — machine j's summand of the ReduceAll."""
        return A_j @ w_j

    def partial_gradient(self, A_j, w_j, z) -> jnp.ndarray:
        """f'_j(w) given the reduced z = Aw."""
        return A_j.T @ self.loss.grad(z, self.y) / self.n + self.lam * w_j

    def partial_hvp(self, A_j, v_j, z, av) -> jnp.ndarray:
        """(f''(w) v)^[j] given reduced z = Aw and av = Av."""
        h = self.loss.hess(z, self.y)
        return A_j.T @ (h * av) / self.n + self.lam * v_j


def make_random_erm(n: int, d: int, loss: str = "squared", lam: float = 1e-2,
                    seed: int = 0, cond: Optional[float] = None) -> ERMProblem:
    """Synthetic ERM instance. If ``cond`` is set, shape A's spectrum to
    roughly that condition number (for controlled kappa experiments)."""
    key = jax.random.PRNGKey(seed)
    ka, kw, kn = jax.random.split(key, 3)
    A = jax.random.normal(ka, (n, d)) / jnp.sqrt(d)
    if cond is not None:
        u, s, vt = jnp.linalg.svd(A, full_matrices=False)
        k = s.shape[0]
        s_new = jnp.geomspace(1.0, 1.0 / jnp.sqrt(cond), k)
        A = (u * s_new) @ vt
    w_true = jax.random.normal(kw, (d,))
    z = A @ w_true
    lf = LOSSES[loss]()
    if loss == "squared":
        y = z + 0.01 * jax.random.normal(kn, (n,))
    else:
        y = jnp.sign(z + 0.01 * jax.random.normal(kn, (n,)))
        y = jnp.where(y == 0, 1.0, y)
    return ERMProblem(A=A, y=y, loss=lf, lam=lam)
