"""Plain reference for ``epsilon-logistic``: the same problem, solved by
straightforward full-vector Nesterov AGD in ``jax.numpy``.

It imports nothing of the program under test.  The data follows the
configuration's recipe from the seed (Gaussian ``A / sqrt(d)``, labels
``sign(A w_true + 0.01 noise)``), built op by op in the order the recipe
states, so the same seed gives the same bits.  The smoothness constant
is 1/4 sigma_max(A)^2 / n + lam, sigma_max by LAPACK on the host
(float32 ``gesdd``, SciPy), the standard way to take it.  Every product
with ``A`` on the device runs at the stated precision:

* ``"highest"``: float32 at full precision, the configuration's own;
* ``"bf16_3x"``: the control, the next precision below: each float32
  operand split into a bfloat16 high and low part and multiplied in three
  bfloat16 passes (the low-by-low product dropped), as XLA's ``high``
  precision does on a TPU.  Written out, so that it means the same on
  every platform.
"""
from __future__ import annotations

import math

import numpy as np
import scipy.linalg
import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "bf16_3x")


def make_data(seed: int, n: int, d: int):
    """(A, y) of the configuration's recipe for ``seed``."""
    key = jax.random.PRNGKey(seed)
    ka, kw, kn = jax.random.split(key, 3)
    A = jax.random.normal(ka, (n, d)) / jnp.sqrt(d)
    w_true = jax.random.normal(kw, (d,))
    z = A @ w_true
    y = jnp.sign(z + 0.01 * jax.random.normal(kn, (n,)))
    y = jnp.where(y == 0, 1.0, y)
    return A, y


def smoothness(A, lam: float) -> float:
    """L = 1/4 sigma_max(A)^2 / n + lam (logistic curvature is <= 1/4)."""
    smax = float(scipy.linalg.svdvals(np.asarray(A))[0])
    return 0.25 * smax ** 2 / A.shape[0] + lam


def expected_ledger(n: int, rounds: int):
    """The paper's communication model for this solve: each round is one
    ReduceAll of the float32 response z = A w in R^n, machines to centre,
    and nothing else.  Returns (typed record stream, round marks) in the
    form the program's ledger reports them."""
    record = ("reduce_all", n, 4 * n, 32 * n, "z=Aw", (n,), "float32",
              "worker->center", False)
    return [record] * rounds, list(range(1, rounds + 1))


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _dot(a, b, precision: str, dims):
    """``lax.dot_general(a, b, dims)`` in float32 at ``precision``."""
    if precision == "highest":
        return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    if precision != "bf16_3x":
        raise ValueError(f"unknown precision {precision!r}; "
                         f"expected one of {PRECISIONS}")
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    dot = lambda u, v: lax.dot_general(u, v, dims,
                                       preferred_element_type=jnp.float32)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


_MV = (((1,), (0,)), ((), ()))      # A @ v
_RMV = (((0,), (0,)), ((), ()))     # A^T @ r


def solve(A, y, lam: float, L: float, rounds: int,
          precision: str = "highest"):
    """Nesterov AGD for the strongly convex case, step 1/L, momentum
    (sqrt(kappa) - 1)/(sqrt(kappa) + 1), from zero.  Returns the iterate
    after ``rounds`` rounds and the objective after each round."""
    n, d = A.shape
    kappa = L / lam
    inv_L = np.float32(1.0 / L)
    beta = np.float32((math.sqrt(kappa) - 1.0) / (math.sqrt(kappa) + 1.0))

    def objective(A, y, w):
        z = _dot(A, w, precision, _MV)
        return (jnp.mean(jnp.logaddexp(0.0, -y * z))
                + 0.5 * lam * jnp.vdot(w, w))

    def run(A, y):
        def body(carry, _):
            x, v = carry
            z = _dot(A, v, precision, _MV)
            r = -y * jax.nn.sigmoid(-y * z)
            g = _dot(A, r, precision, _RMV) / n + lam * v
            x_new = v - inv_L * g
            v_new = x_new + beta * (x_new - x)
            return (x_new, v_new), objective(A, y, x_new)

        zero = jnp.zeros((d,), jnp.float32)
        (x, _), f = lax.scan(body, (zero, zero), None, length=rounds)
        return x, f

    x, f = jax.jit(run)(A, y)
    return np.asarray(x), np.asarray(f, dtype=np.float64)
