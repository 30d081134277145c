"""Plain reference for ``thm2-certify``: each certification spec solved
and judged by straightforward ``jax.numpy`` and ``numpy``.

It imports nothing of the program under test.  For a spec (kappa,
algorithm, wire channel) it builds the paper's Theorem 2 hard chain
(Eq. 7: tridiagonal, last diagonal entry (sqrt(kappa) + 3)/(sqrt(kappa)
+ 1), scaled by c = lam (kappa - 1)/4) written as a least-squares ERM:
A = sqrt(n) (c T)^{1/2} by eigendecomposition in float64, labels
sqrt(n) y with A y = c e_1 solved by least squares, n = d.  It runs the
algorithm over the m contiguous feature blocks for the round budget:

* each round, machine j uploads z_j = A_j v_j (the ``fp16`` wire rounds
  it to half precision and back, nearest even); the centre sums them;
* the gradient block is A_j^T (z - y) / n + lam v_j;
* ``dagd``: Nesterov AGD, step 1/L, momentum (sqrt(k) - 1)/(sqrt(k) + 1)
  with k = L / lam; ``dgd``: gradient steps of 2 / (L + lam);

with L = sigma_max(A)^2 / n + lam (LAPACK ``gesdd`` on the float32 A).
After every round it takes the suboptimality f(w_k) - f(w*), w*(i) =
q^i with q = (sqrt(kappa) - 1)/(sqrt(kappa) + 1), and reads the first
round at which it is at most eps.  The Theorem 2 bound is
(sqrt(kappa) - 1)/4 log(lam |w*|^2 / ((sqrt(kappa) + 1) eps)), and a
spec is certified when its measured rounds (or, unreached, its budget)
reach the bound.  Every product with A runs at the stated precision
(``"highest"``, or the control ``"bf16_3x"``: three bfloat16 passes,
as XLA's ``high`` does on a TPU).
"""
from __future__ import annotations

import math

import numpy as np
import scipy.linalg
import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "bf16_3x")


def chain_erm(d: int, kappa: float, lam: float):
    """(A, y) float32, n = d, of the Theorem 2 chain as an ERM."""
    T = np.zeros((d, d))
    i = np.arange(d)
    T[i, i] = 2.0
    T[i[:-1], i[:-1] + 1] = -1.0
    T[i[:-1] + 1, i[:-1]] = -1.0
    rk = np.sqrt(kappa)
    T[d - 1, d - 1] = (rk + 3.0) / (rk + 1.0)
    c = lam * (kappa - 1.0) / 4.0
    evals, evecs = np.linalg.eigh(T)
    B = (evecs * np.sqrt(np.clip(c * np.clip(evals, 0.0, None), 0, None))) \
        @ evecs.T
    rhs = np.zeros(d)
    rhs[0] = c
    y = np.linalg.lstsq(B.T, rhs, rcond=None)[0]
    root = np.float32(np.sqrt(d))
    return B.astype(np.float32) * root, y.astype(np.float32) * root


def w_star(d: int, kappa: float) -> np.ndarray:
    rk = math.sqrt(kappa)
    q = np.float32((rk - 1.0) / (rk + 1.0))
    return q ** np.arange(1, d + 1, dtype=np.float32)


def smoothness(A, lam: float) -> float:
    smax = float(scipy.linalg.svdvals(np.asarray(A))[0])
    return smax ** 2 / A.shape[0] + lam


def thm2_bound(kappa: float, lam: float, norm_w_star: float,
               eps: float) -> float:
    rk = math.sqrt(kappa)
    arg = lam * norm_w_star ** 2 / ((rk + 1.0) * eps)
    return 0.0 if arg <= 1.0 else max(0.0, (rk - 1.0) / 4.0 * math.log(arg))


def expected_ledger(n: int, rounds: int, channel: str):
    """The paper's model: each round one ReduceAll of the float32
    response in R^n, machines to centre; the ``fp16`` wire prices it at
    16 bits an element."""
    bits = 16 * n if channel == "fp16" else 32 * n
    record = ("reduce_all", n, 4 * n, bits, "z=Aw", (n,), "float32",
              "worker->center", False)
    return [record] * rounds, list(range(1, rounds + 1))


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _dot(a, b, precision: str, dims):
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


_MV = (((1,), (0,)), ((), ()))
_RMV = (((0,), (0,)), ((), ()))


def _solve_batch(A, y, fstar, inv_step, beta, *, m, rounds, lam, half,
                 precision):
    """vmapped over specs of one (algorithm, wire): (w_final, gaps)."""
    n, d = A.shape[-2], A.shape[-1]
    blocks = np.array_split(np.arange(d), m)

    def one(A, y, fstar, inv_step, beta):
        def objective(w):
            r = _dot(A, w, precision, _MV) - y
            return 0.5 * jnp.mean(r * r) + 0.5 * lam * jnp.vdot(w, w)

        def body(carry, _):
            x, v = carry
            parts = [_dot(A[:, b], v[b], precision, _MV) for b in blocks]
            if half:
                parts = [p.astype(jnp.float16).astype(jnp.float32)
                         for p in parts]
            z = jnp.sum(jnp.stack(parts), axis=0)
            g = _dot(A, z - y, precision, _RMV) / n + lam * v
            x_new = v - inv_step * g
            v_new = x_new + beta * (x_new - x)
            return (x_new, v_new), objective(x_new) - fstar

        zero = jnp.zeros((d,), jnp.float32)
        (x, _), gaps = lax.scan(body, (zero, zero), None, length=rounds)
        return x, gaps

    return jax.jit(jax.vmap(one))(A, y, fstar, inv_step, beta)


def certify(specs, *, d: int, lam: float, m: int, rounds: int, eps: float,
            precision: str = "highest"):
    """Each spec, a dict with ``kappa``, ``algorithm`` (dagd | dgd) and
    ``channel`` (identity | fp16), judged: a list of dicts with ``w``,
    ``measured_rounds`` (None if eps is never reached), ``bound_rounds``
    and ``certified``, in the order given."""
    out = [None] * len(specs)
    groups = {}
    for i, s in enumerate(specs):
        groups.setdefault((s["algorithm"], s["channel"]), []).append(i)
    for (algo, channel), idx in groups.items():
        rows = []
        for i in idx:
            kappa = float(specs[i]["kappa"])
            A, y = chain_erm(d, kappa, lam)
            ws = w_star(d, kappa)
            L = smoothness(A, lam)
            fstar = float(_objective_np(A, y, ws, lam))
            if algo == "dagd":
                k = L / lam
                inv_step = 1.0 / L
                beta = (math.sqrt(k) - 1.0) / (math.sqrt(k) + 1.0)
            elif algo == "dgd":
                inv_step, beta = 2.0 / (L + lam), 0.0
            else:
                raise ValueError(f"no reference for algorithm {algo!r}")
            norm = float(np.linalg.norm(ws))
            rows.append((A, y, fstar, inv_step, beta, kappa, norm))
        A, y, fstar, inv_step, beta = (
            jnp.asarray(np.stack([r[j] for r in rows]).astype(np.float32))
            for j in range(5))
        w, gaps = _solve_batch(A, y, fstar, inv_step, beta, m=m,
                               rounds=rounds, lam=lam,
                               half=channel == "fp16", precision=precision)
        w, gaps = np.asarray(w), np.asarray(gaps)
        for j, i in enumerate(idx):
            hits = np.nonzero(gaps[j] <= eps)[0]
            measured = int(hits[0]) + 1 if hits.size else None
            bound = thm2_bound(rows[j][5], lam, rows[j][6], eps)
            certified = (measured >= bound if measured is not None
                         else (True if rounds >= bound else None))
            out[i] = dict(w=w[j], measured_rounds=measured,
                          bound_rounds=bound, certified=certified)
    return out


def _objective_np(A, y, w, lam):
    """f at w in float64 on the host (for f*, outside the timed loop)."""
    A64, y64, w64 = (np.asarray(a, np.float64) for a in (A, y, w))
    r = A64 @ w64 - y64
    return 0.5 * np.mean(r * r) + 0.5 * lam * float(w64 @ w64)
