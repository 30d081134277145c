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
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


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
        on the CPU bit for bit.  An A that spans several devices never
        comes to the host: sigma_max^2 is the largest eigenvalue of its
        Gram matrix (``gram``)."""
        if spans_devices(self.A):
            smax_sq = float(np.linalg.eigvalsh(gram(self.A))[-1])
        else:
            smax_sq = scipy.linalg.svdvals(np.asarray(self.A))[0] ** 2
        return float(self.loss.smoothness * smax_sq / self.n + self.lam)

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


def spans_devices(x) -> bool:
    """Whether the array ``x`` is laid out over more than one device."""
    sharding = getattr(x, "sharding", None)
    return sharding is not None and len(sharding.device_set) > 1


# Rows of a sharded A that ``gram`` multiplies, or that the sharded draws
# gather onto every device, at once.
CHUNK_ROWS = 1 << 16


def gram(A) -> np.ndarray:
    """A^T A in float64 on the host, for an A that may span devices.

    Each chunk of ``CHUNK_ROWS`` rows is multiplied on the devices where
    it lies, at ``Precision.HIGHEST`` (d x d float32 partial sums); the
    host adds the chunks in float64.  Only d x d values leave the
    devices, never A."""
    n, d = A.shape
    rows = CHUNK_ROWS

    def product(B):
        return lax.dot_general(B, B, (((0,), (0,)), ((), ())),
                               precision=lax.Precision.HIGHEST)

    chunk = jax.jit(lambda A, s: product(
        lax.dynamic_slice_in_dim(A, s, rows, axis=0)))
    G = np.zeros((d, d), np.float64)
    full = n // rows
    for k in range(full):
        G += np.asarray(chunk(A, k * rows), np.float64)
    if n > full * rows:
        G += np.asarray(jax.jit(product)(A[full * rows:]), np.float64)
    return G


def _sharded_draws(ka, kw, kn, n: int, d: int, mesh: Mesh):
    """The recipe's random draws and response z = A w_true with A laid
    out column-sharded over ``mesh`` (one axis) and the vectors
    replicated, bit for bit the draws of the one-device recipe.  The
    threefry generator is partitionable, so a draw's bits do not depend
    on its layout; the elementwise steps run op by op as the recipe's
    do; z is the recipe's dot, taken on chunks of ``CHUNK_ROWS`` whole
    rows gathered onto every device in turn (each row's sum runs over
    all d features, as on one device, and no device holds more than A's
    block and one chunk)."""
    axis, = mesh.axis_names
    every = NamedSharding(mesh, P())

    def draw(key, shape, spec):
        return jax.jit(lambda k: jax.random.normal(k, shape),
                       out_shardings=NamedSharding(mesh, spec))(key)

    A = draw(ka, (n, d), P(None, axis)) / jnp.sqrt(d)
    w_true = draw(kw, (d,), P())
    rows = min(CHUNK_ROWS, n)
    take = jax.jit(lambda A, s: lax.dynamic_slice_in_dim(A, s, rows),
                   out_shardings=every)
    dot = jax.jit(lambda B, w: B @ w)
    z = [dot(take(A, s), w_true) for s in range(0, n - rows + 1, rows)]
    if n % rows:
        tail = jax.jit(lambda A: A[n - n % rows:], out_shardings=every)
        z.append(dot(tail(A), w_true))
    noise = draw(kn, (n,), P())
    return A, w_true, jnp.concatenate(z), noise


def random_erm_data(n: int, d: int, loss: str = "squared", seed: int = 0,
                    cond: Optional[float] = None,
                    mesh: Optional[Mesh] = None):
    """(A, y, w_true) of ``make_random_erm``'s recipe.  With ``mesh``
    (a one-axis mesh of the machines' devices) A is built column-sharded
    over it and y, w_true replicated, never whole on one device; the
    bits are the one-device recipe's."""
    key = jax.random.PRNGKey(seed)
    ka, kw, kn = jax.random.split(key, 3)
    if mesh is not None:
        if cond is not None:
            raise ValueError("cond shapes A's spectrum by an SVD of the "
                             "whole A; it is not available with mesh")
        A, w_true, z, noise = _sharded_draws(ka, kw, kn, n, d, mesh)
    else:
        A = jax.random.normal(ka, (n, d)) / jnp.sqrt(d)
        if cond is not None:
            u, s, vt = jnp.linalg.svd(A, full_matrices=False)
            k = s.shape[0]
            s_new = jnp.geomspace(1.0, 1.0 / jnp.sqrt(cond), k)
            A = (u * s_new) @ vt
        w_true = jax.random.normal(kw, (d,))
        z = A @ w_true
        noise = jax.random.normal(kn, (n,))
    if loss == "squared":
        y = z + 0.01 * noise
    else:
        y = jnp.sign(z + 0.01 * noise)
        y = jnp.where(y == 0, 1.0, y)
    return A, y, w_true


def make_random_erm(n: int, d: int, loss: str = "squared", lam: float = 1e-2,
                    seed: int = 0, cond: Optional[float] = None,
                    mesh: Optional[Mesh] = None) -> ERMProblem:
    """Synthetic ERM instance. If ``cond`` is set, shape A's spectrum to
    roughly that condition number (for controlled kappa experiments).
    With ``mesh``, A is built column-sharded over it
    (``random_erm_data``)."""
    A, y, _ = random_erm_data(n, d, loss=loss, seed=seed, cond=cond,
                              mesh=mesh)
    return ERMProblem(A=A, y=y, loss=LOSSES[loss](), lam=lam)
