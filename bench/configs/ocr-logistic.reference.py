"""Plain reference for ``ocr-logistic``: the same problem, solved by
straightforward full-vector Nesterov AGD in ``jax.numpy`` on a data
matrix that lies column-sharded over every device of the host (16.2 GB
of float32 does not fit one chip); XLA partitions each product.

It imports nothing of the program under test.  The data follows the
configuration's recipe from the seed (Gaussian ``A / sqrt(d)``, labels
``sign(A w_true + 0.01 noise)``) with the bits of the one-device recipe:
the threefry generator is partitionable, so each draw is the same
whatever its layout; the elementwise steps run op by op; ``A w_true`` is
taken on chunks of whole rows copied to every device, each row's dot
over all d features as on one device.  The smoothness constant is 1/4 lambda_max(A^T A) / n +
lam, with A^T A formed on the devices in row chunks at full float32
precision, summed in float64 on the host, and lambda_max by LAPACK
``eigvalsh``.  Every product with ``A`` runs at the stated precision:

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
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PRECISIONS = ("highest", "bf16_3x")
GRAM_ROWS = 1 << 16


def _mesh() -> Mesh:
    return Mesh(np.array(jax.devices()), ("cols",))


def make_data(seed: int, n: int, d: int):
    """(A, y) of the configuration's recipe for ``seed``: A column-sharded
    over every device, y replicated."""
    mesh = _mesh()

    def on(spec):
        return NamedSharding(mesh, spec)

    def normal(key, shape, spec):
        return jax.jit(lambda k: jax.random.normal(k, shape),
                       out_shardings=on(spec))(key)

    key = jax.random.PRNGKey(seed)
    ka, kw, kn = jax.random.split(key, 3)
    A = normal(ka, (n, d), P(None, "cols")) / jnp.sqrt(d)
    w_true = normal(kw, (d,), P())
    # A w_true a chunk of whole rows at a time, each chunk copied to
    # every device and multiplied there as on one device
    rows = min(GRAM_ROWS, n)
    chunk = jax.jit(lambda A, s: lax.dynamic_slice_in_dim(A, s, rows),
                    out_shardings=on(P()))
    mv = jax.jit(lambda B, w: B @ w)
    z = [mv(chunk(A, s), w_true) for s in range(0, n - rows + 1, rows)]
    if n % rows:
        z.append(mv(jax.jit(lambda A: A[n - n % rows:],
                            out_shardings=on(P()))(A), w_true))
    y = jnp.sign(jnp.concatenate(z) + 0.01 * normal(kn, (n,), P()))
    y = jnp.where(y == 0, 1.0, y)
    return A, y


def smoothness(A, lam: float) -> float:
    """L = 1/4 lambda_max(A^T A) / n + lam (logistic curvature is
    <= 1/4), with A^T A summed over row chunks in float64."""
    n, d = A.shape
    rows = min(GRAM_ROWS, n)

    def gram(B):
        return lax.dot_general(B, B, (((0,), (0,)), ((), ())),
                               precision=lax.Precision.HIGHEST)

    chunk = jax.jit(lambda A, s: gram(lax.dynamic_slice_in_dim(A, s, rows)))
    G = np.zeros((d, d))
    for start in range(0, n - rows + 1, rows):
        G += np.asarray(chunk(A, start), np.float64)
    if n % rows:
        G += np.asarray(jax.jit(gram)(A[n - n % rows:]), np.float64)
    return 0.25 * float(np.linalg.eigvalsh(G)[-1]) / n + lam


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
