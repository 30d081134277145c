"""Instance builders: the paper's hard functions and real workloads,
packaged uniformly so the sweep runner can treat them interchangeably.

An ``InstanceBundle`` carries a concrete ERM problem, its feature
partition, the objective to measure suboptimality against (which may
include a separable regularizer psi), the reference optimum, and the
parameters a certifying bound needs (kappa, L, n, |w*|).

``hard=True`` marks the Theorem-2/3/4 constructions: on those, every
algorithm's measured rounds-to-eps is REQUIRED to sit above the closed-
form bound (the certification inequality). Real workloads (lasso,
logistic, random ridge) set ``hard=False``: the bounds are worst-case
over function classes, so on an easy instance measured < bound is
legitimate — the overlay is reported as context, not as a certificate.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import math
from typing import Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from repro.core import (ChainInstance, ERMProblem, make_random_erm,
                        squared_loss)
from repro.core.erm import gram, spans_devices
from repro.core.algorithms import soft_threshold
from repro.core.engine import hoisted_jit
from repro.core.partition import FeaturePartition, even_partition
from repro.metrics.spans import span

from .registry import AlgoContext


@dataclasses.dataclass(frozen=True)
class InstanceBundle:
    kind: str
    hard: bool                      # certification inequality applies
    prob: ERMProblem
    part: FeaturePartition
    ctx: AlgoContext
    objective: Callable             # w (d,) -> scalar; includes psi if any
    fstar: Optional[float]          # None => fixed-rounds use only
    wstar_norm: Optional[float]
    params: Dict[str, float]        # what the bounds + report tables need
                                    # (may hold DERIVED values, e.g. the
                                    # thm4 kappa is the embedded ERM's own)
    build_params: Optional[Dict[str, object]] = None
                                    # the verbatim builder inputs, stamped
                                    # by build_instance; repro.api.plan
                                    # checks a supplied bundle against the
                                    # spec's instance_params with these

    @property
    def label(self) -> str:
        inner = ", ".join(f"{k}={v:g}" if isinstance(v, float) else f"{k}={v}"
                          for k, v in self.params.items())
        return f"{self.kind}({inner})"


# --------------------------------------------------------------------------
# Shared construction helpers
# --------------------------------------------------------------------------

# The build's parts, each in a span of its own (``repro.metrics.spans``):
# the data (timed until it is on the device), the smoothness bound L,
# the per-block constants and the reference optimum f*.

def _make_context(prob: ERMProblem, part: FeaturePartition, L: float,
                  prox: Optional[Callable] = None) -> AlgoContext:
    """Every constant the registered adapters may ask for, given ``L =
    _smoothness(prob)`` (each builder computes it once).  The per-block
    bounds and the per-component bound are computed when an adapter
    first reads them (only ``bcd`` and ``dsvrg`` do)."""
    return AlgoContext(L=L, lam=prob.lam, m=part.m, n=prob.n, d=prob.d,
                       loss_name=prob.loss.name, prox=prox,
                       L_max_fn=functools.partial(_component_L_max, prob),
                       block_L_fn=functools.partial(_block_L, prob, part))


def _component_L_max(prob: ERMProblem) -> float:
    """max_i ell''_max |a_i|^2 + lam, the incremental family's bound."""
    with span("repro.instance.block_norms"):
        sm = prob.loss.smoothness
        if spans_devices(prob.A):
            # each device sums its columns' squares; one R^n reduce
            rows = jax.jit(lambda A: jnp.max(jnp.sum(A ** 2, axis=1)))
            return float(rows(prob.A)) * sm + prob.lam
        A = np.asarray(prob.A)
        return float(np.max(np.sum(A ** 2, axis=1)) * sm + prob.lam)


def _block_L(prob: ERMProblem, part: FeaturePartition) -> np.ndarray:
    """(m, 1) per-block bounds ell''_max sigma_max(A_j)^2 / n + lam."""
    with span("repro.instance.block_norms"):
        sm = prob.loss.smoothness
        blocks = zip(part.offsets, part.block_sizes)
        if spans_devices(prob.A):
            # sigma_max(A_j)^2 is the top eigenvalue of A^T A's block jj
            G = gram(prob.A)
            sq = [np.linalg.eigvalsh(G[o:o + b, o:o + b])[-1]
                  for o, b in blocks]
        else:
            A = np.asarray(prob.A)
            sq = [np.linalg.norm(A[:, o:o + b], 2) ** 2 for o, b in blocks]
        return np.array([sm * s / prob.n + prob.lam
                         for s in sq]).reshape(-1, 1)


def _ready(prob: ERMProblem) -> ERMProblem:
    jax.block_until_ready((prob.A, prob.y))
    return prob


def _smoothness(prob: ERMProblem) -> float:
    with span("repro.instance.smoothness"):
        return prob.smoothness_bound()


def _fstar(objective: Callable, wstar) -> float:
    """f* at a known minimizer (closed form or reference solve)."""
    with span("repro.instance.reference_solve"):
        return float(objective(wstar))


def chain_erm(d: int, kappa: float, lam: float):
    """The Theorem-2 hard chain function embedded exactly as a ridge
    least-squares ERM (so the generic feature-partitioned algorithms run
    on it unchanged)."""
    ci = ChainInstance(d=d, kappa=kappa, lam=lam)
    B, y, lam_ = ci.as_erm_data()
    n = B.shape[0]
    prob = ERMProblem(A=jnp.asarray(B) * np.sqrt(n),
                      y=jnp.asarray(y) * np.sqrt(n),
                      loss=squared_loss(), lam=lam_)
    return ci, prob


def smooth_chain_erm(d: int, L: float):
    """The Theorem-3 hard function (Nesterov's smooth chain, lam = 0)
    embedded as an un-regularized least-squares ERM. Returns the problem
    and the closed-form minimizer w*(i) = 1 - i/(d+1)."""
    A = np.zeros((d, d))
    idx = np.arange(d)
    A[idx, idx] = 2.0
    A[idx[:-1], idx[:-1] + 1] = -1.0
    A[idx[:-1] + 1, idx[:-1]] = -1.0
    c = L / 4.0
    evals, evecs = np.linalg.eigh(A)
    B = (evecs * np.sqrt(np.clip(c * evals, 0, None))) @ evecs.T
    rhs = np.zeros(d)
    rhs[0] = c
    y = np.linalg.lstsq(B.T, rhs, rcond=None)[0]
    n = d
    prob = ERMProblem(A=jnp.asarray(B) * np.sqrt(n),
                      y=jnp.asarray(y) * np.sqrt(n),
                      loss=squared_loss(), lam=0.0)
    wstar = 1.0 - np.arange(1, d + 1) / (d + 1.0)
    return prob, jnp.asarray(wstar)


def _reference_solution(prob: ERMProblem, iters: int, L: float,
                        prox: Optional[Callable] = None) -> jnp.ndarray:
    """High-accuracy reference minimizer for workloads with no closed form:
    full-vector (non-distributed) FISTA / accelerated gradient, jitted."""
    with span("repro.instance.reference_solve"):    # the dispatch; the
        return _accelerated_solve(prob, iters, L, prox)  # wait is _fstar's


def _accelerated_solve(prob: ERMProblem, iters: int, L: float,
                       prox: Optional[Callable]) -> jnp.ndarray:
    lam = prob.lam
    grad = jax.grad(prob.value) if prox is None else prob.gradient
    px = prox if prox is not None else (lambda w, s: w)
    if lam > 0:
        kap = L / lam
        beta = (math.sqrt(kap) - 1.0) / (math.sqrt(kap) + 1.0)

        def body(_, carry):
            x, y = carry
            x_new = px(y - grad(y) / L, 1.0 / L)
            return x_new, x_new + beta * (x_new - x)

        x0 = jnp.zeros((prob.d,))
        x, _ = hoisted_jit(lambda c: lax.fori_loop(0, iters, body, c))(
            (x0, x0))
        return x

    def body(_, carry):
        x, y, t = carry
        x_new = px(y - grad(y) / L, 1.0 / L)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_new = x_new + ((t - 1.0) / t_new) * (x_new - x)
        return x_new, y_new, t_new

    x0 = jnp.zeros((prob.d,))
    x, _, _ = hoisted_jit(lambda c: lax.fori_loop(0, iters, body, c))(
        (x0, x0, jnp.asarray(1.0)))
    return x


# --------------------------------------------------------------------------
# Hard instances (certification applies)
# --------------------------------------------------------------------------

def build_thm2_chain(d: int = 160, kappa: float = 64.0, lam: float = 0.5,
                     m: int = 4) -> InstanceBundle:
    """Theorem-2 hard instance: lam-strongly-convex chain with condition
    number kappa; exact minimizer w*(i) = q^i."""
    with span("repro.instance.data"):
        ci, prob = chain_erm(d, kappa, lam)
        _ready(prob)
    part = even_partition(prob.d, m)
    wstar = jnp.asarray(ci.w_star())
    fstar = _fstar(prob.value, wstar)
    return InstanceBundle(
        kind="thm2_chain", hard=True, prob=prob, part=part,
        ctx=_make_context(prob, part, _smoothness(prob)),
        objective=prob.value,
        fstar=fstar, wstar_norm=float(jnp.linalg.norm(wstar)),
        params=dict(d=d, kappa=kappa, lam=lam, m=m, n=prob.n))


def build_thm3_chain(d: int = 128, L: float = 1.0, m: int = 4
                     ) -> InstanceBundle:
    """Theorem-3 hard instance: smooth convex chain, lam = 0."""
    with span("repro.instance.data"):
        prob, wstar = smooth_chain_erm(d, L)
        _ready(prob)
    part = even_partition(d, m)
    fstar = _fstar(prob.value, wstar)
    return InstanceBundle(
        kind="thm3_chain", hard=True, prob=prob, part=part,
        ctx=_make_context(prob, part, _smoothness(prob)),
        objective=prob.value,
        fstar=fstar, wstar_norm=float(jnp.linalg.norm(wstar)),
        params=dict(d=d, L=L, m=m, n=prob.n))


def build_thm4_separable(n: int = 32, kappa: float = 64.0, lam: float = 0.5,
                         m: int = 4) -> InstanceBundle:
    """Theorem-4 hard instance for the incremental family: the chain
    function on d = n coordinates, so the ERM has n components and each
    stochastic step touches one (Definition 3.2's model). The certifying
    kappa is the ERM's own condition number L/lam."""
    with span("repro.instance.data"):
        ci, prob = chain_erm(d=n, kappa=kappa, lam=lam)
        _ready(prob)
    part = even_partition(prob.d, m)
    wstar = jnp.asarray(ci.w_star())
    fstar = _fstar(prob.value, wstar)
    L = _smoothness(prob)
    kappa_erm = L / prob.lam
    return InstanceBundle(
        kind="thm4_separable", hard=True, prob=prob, part=part,
        ctx=_make_context(prob, part, L), objective=prob.value,
        fstar=fstar, wstar_norm=float(jnp.linalg.norm(wstar)),
        params=dict(n=n, kappa=kappa_erm, lam=lam, m=m, d=prob.d))


# --------------------------------------------------------------------------
# Real workloads (bounds overlaid as context; hard=False)
# --------------------------------------------------------------------------

def build_lasso(n: int = 128, d: int = 256, m: int = 4, tau: float = 2e-3,
                k_true: int = 10, seed: int = 0,
                ref_iters: int = 20000) -> InstanceBundle:
    """Sparse-recovery lasso: F(w) = 1/2n |Aw - y|^2 + tau |w|_1. The prox
    is block-local, so the round budget stays one R^n ReduceAll."""
    with span("repro.instance.data"):
        rng = np.random.RandomState(seed)
        A = rng.randn(n, d) / np.sqrt(d)
        w_true = np.zeros(d)
        idx = rng.choice(d, k_true, replace=False)
        w_true[idx] = rng.randn(k_true) * 3
        y = A @ w_true + 0.01 * rng.randn(n)
        prob = _ready(ERMProblem(A=jnp.asarray(A), y=jnp.asarray(y),
                                 loss=squared_loss(), lam=0.0))
    part = even_partition(d, m)
    prox = soft_threshold(tau)

    def objective(w):
        return prob.value(w) + tau * jnp.sum(jnp.abs(w))

    L = _smoothness(prob)
    wref = _reference_solution(prob, ref_iters, L, prox=prox)
    return InstanceBundle(
        kind="lasso", hard=False, prob=prob, part=part,
        ctx=_make_context(prob, part, L, prox=prox), objective=objective,
        fstar=_fstar(objective, wref),
        wstar_norm=float(jnp.linalg.norm(wref)),
        params=dict(n=n, d=d, m=m, tau=tau, L=L))


def build_logistic(n: int = 256, d: int = 96, m: int = 4, lam: float = 1e-2,
                   seed: int = 0, ref_iters: int = 20000,
                   mesh: Optional[Mesh] = None) -> InstanceBundle:
    """Ridge-regularized logistic regression on synthetic separable-ish
    data — the paper's motivating GLM workload.  With ``mesh`` (the
    machines' devices on one axis) A is built column-sharded over it,
    each machine's columns on its own device, and every constant and the
    reference solve are computed where the blocks lie."""
    with span("repro.instance.data"):
        prob = _ready(make_random_erm(n=n, d=d, loss="logistic", lam=lam,
                                      seed=seed, mesh=mesh))
    part = even_partition(d, m)
    L = _smoothness(prob)
    wref = _reference_solution(prob, ref_iters, L)
    kappa = L / lam
    return InstanceBundle(
        kind="logistic", hard=False, prob=prob, part=part,
        ctx=_make_context(prob, part, L), objective=prob.value,
        fstar=_fstar(prob.value, wref),
        wstar_norm=float(jnp.linalg.norm(wref)),
        params=dict(n=n, d=d, m=m, lam=lam, kappa=kappa))


def build_random_ridge(n: int = 256, d: int = 64, m: int = 8,
                       lam: float = 1e-2, seed: int = 1) -> InstanceBundle:
    """Random ridge ERM for fixed-round communication costing (no fstar:
    used by the comm-cost sweeps, which never measure rounds-to-eps)."""
    with span("repro.instance.data"):
        prob = _ready(make_random_erm(n=n, d=d, loss="squared", lam=lam,
                                      seed=seed))
    part = even_partition(d, m)
    return InstanceBundle(
        kind="random_ridge", hard=False, prob=prob, part=part,
        ctx=_make_context(prob, part, _smoothness(prob)),
        objective=prob.value,
        fstar=None, wstar_norm=None,
        params=dict(n=n, d=d, m=m, lam=lam))


INSTANCE_BUILDERS: Dict[str, Callable[..., InstanceBundle]] = {
    "thm2_chain": build_thm2_chain,
    "thm3_chain": build_thm3_chain,
    "thm4_separable": build_thm4_separable,
    "lasso": build_lasso,
    "logistic": build_logistic,
    "random_ridge": build_random_ridge,
}


# (n, d, m) of each builder's instance, from the builder's parameters
# (defaults applied): the shape a placement is chosen from before
# anything is built.  The chains are square: d x d (n x n for thm4).
INSTANCE_SHAPES: Dict[str, Callable[..., Tuple[int, int, int]]] = {
    "thm2_chain": lambda d, m, **_: (d, d, m),
    "thm3_chain": lambda d, m, **_: (d, d, m),
    "thm4_separable": lambda n, m, **_: (n, n, m),
    "lasso": lambda n, d, m, **_: (n, d, m),
    "logistic": lambda n, d, m, **_: (n, d, m),
    "random_ridge": lambda n, d, m, **_: (n, d, m),
}


def instance_shape(kind: str, params: dict
                   ) -> Optional[Tuple[int, int, int]]:
    """(n, d, m) of the instance ``kind`` builds from ``params``; None
    for an unknown kind or parameters its builder does not take (plan
    validation names those)."""
    if kind not in INSTANCE_BUILDERS:
        return None
    try:
        bound = inspect.signature(INSTANCE_BUILDERS[kind]).bind(**params)
    except TypeError:
        return None
    bound.apply_defaults()
    return tuple(int(x) for x in INSTANCE_SHAPES[kind](**bound.arguments))


def build_instance(kind: str, mesh: Optional[Mesh] = None,
                   **params) -> InstanceBundle:
    """Build instance ``kind`` from ``params``.  ``mesh`` places the
    data column-sharded over the machines' devices, for the builders
    that take one (``builds_sharded``); it is a placement, not a
    parameter of the problem, so ``build_params`` leaves it out."""
    try:
        builder = INSTANCE_BUILDERS[kind]
    except KeyError:
        raise KeyError(f"unknown instance kind {kind!r}; known: "
                       f"{sorted(INSTANCE_BUILDERS)}") from None
    kwargs = params if mesh is None else dict(params, mesh=mesh)
    with span("repro.instance_build", kind=kind):
        bundle = builder(**kwargs)
    return dataclasses.replace(bundle, build_params=dict(params))


def builds_sharded(kind: str) -> bool:
    """Whether the builder of ``kind`` can build its data column-sharded
    over a mesh."""
    return "mesh" in inspect.signature(INSTANCE_BUILDERS[kind]).parameters
