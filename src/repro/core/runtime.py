"""Distributed runtime: one algorithm codebase, two execution backends.

Algorithms (``core.algorithms``) are written against the ``DistERM``
interface, which exposes exactly the oracles the paper's Definition 1
allows, with every cross-machine interaction going through a metered
communicator:

    response(w)        z = A w            — ONE ReduceAll of an R^n vector
    pgrad(w, z)        f'_j(w) per block  — local
    phvp(v, z, av)     (f''(w) v)^[j]     — local given reduced Av
    dot(u, v)          <u, v> global      — ONE ReduceAll of a scalar
    end_round()        round boundary

Backends:
  * ``LocalDistERM`` — m simulated machines; per-machine blocks stacked on a
    leading axis (m, ...). Reference semantics, used by tests/benchmarks.
  * ``ShardedDistERM`` — identical math with machine j = slice j of a mesh
    axis; constructed *inside* a ``shard_map`` body. ``ShardedProgram``
    takes column-sharded data where it lies on a real mesh (or shards a
    one-device A), compiles any algorithm through it once and runs it
    (front-ended by ``repro.api``'s sharded placement).

The two backends are required to produce bit-comparable iterates (up to
reduction order), which ``tests/test_runtime_parity.py`` asserts.

Orthogonal to the execution backend is the **oracle backend**: how the
per-machine GEMVs inside ``response``/``pgrad``/``phvp`` are computed.
Each backend is an ``OracleBackend`` strategy object (resolved once per
run by ``repro.api._resolve``, never re-dispatched per call):

  * ``"einsum"`` — plain ``jnp`` contractions (XLA decides the schedule);
    the CPU default and the reference semantics.
  * ``"kernel"`` — the Pallas GEMV kernels in ``repro.kernels``
    (``feature_matvec``/``feature_rmatvec``/``feature_hvp``), ``vmap``-ed
    over the stacked machine axis in local mode and applied directly to
    the local shard inside ``shard_map``.
  * ``"fused"`` — the kernel path with epilogue-fused oracles
    (``fused_pgrad``/``fused_phvp``: the ``/n + lam v`` + mask epilogue
    folded into the contraction's last block) plus the whole-round
    ``round_step()`` capability: program builders that recognise their
    round as response -> pgrad -> block-local update hand the update to
    ``LocalDistERM.fused_round_step`` and, when the cell qualifies
    (local placement, in-kernel channel, single-tile A_j block), run the
    entire round as ONE Pallas kernel per machine with the wire channel
    applied in the same pass that emits the upload
    (``kernels/fused_round.py``); otherwise they fall back to the
    composed oracles.  The TPU default under ``auto``.

The paper meters communication *rounds*, never local FLOPs, so the oracle
backend MUST be invisible to the ``CommLedger`` — the conformance suite
(``tests/test_ledger_invariance.py``) pins that invariant (for ``fused``
it pins identical streams and round marks against ``kernel`` wherever
the whole-round kernel engages, and iterates equal to f32 rounding).

A third orthogonal axis is the **round engine** (``core.engine``): whether
an algorithm's rounds run as a per-call Python loop (``"python"``) or as
one ``lax.scan``-compiled XLA program (``"scan"``).  ``ShardedProgram``
accepts a step-form ``RoundProgram`` builder to compile the whole
multi-round run, in-scan gap measure included, inside the ``shard_map``
body.  Segment capture, round-index threading and the ledger replay
are ``core.engine``'s, shared with the local engines, so the sharded
ledger is the same per-call stream the python loop produces.

All three axes are front-ended by ``repro.api``: a ``RunSpec`` names
placement/backend/engine declaratively, ``plan`` resolves the ``auto``
choices through the single capability resolver, and the resulting
``ExecutionPlan`` drives the machinery here.  The per-call knobs on the
runtime classes remain for direct use; the PR-4 ``run_sharded`` kwargs
shim is retired (it raises, naming the ``RunSpec`` replacement).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .comm import CommLedger, LocalCommunicator, ShardMapCommunicator
from .erm import ERMProblem, GLMLoss
from .partition import FeaturePartition
from ..kernels import ops as kops
from ..metrics.spans import span


# --------------------------------------------------------------------------
# Oracle-backend dispatch
# --------------------------------------------------------------------------

# Canonical list lives in repro.api._resolve (the single resolver);
# mirrored here because this module cannot import repro.api at load time
# (repro.api.plan imports this module). tests/test_api.py pins equality.
ORACLE_BACKENDS = ("einsum", "kernel", "fused")


def resolve_oracle_backend(backend: Optional[str] = None) -> str:
    """Resolve an oracle-backend choice to a member of
    ``ORACLE_BACKENDS``.

    Delegates to the single capability resolver in ``repro.api``
    (env var consulted at call time, then the platform: kernels compile
    for TPU, interpret-mode elsewhere).  Kept under its historical name
    so direct ``LocalDistERM``/``ShardedDistERM`` construction still
    resolves; planned runs (``repro.api.plan``) arrive here with the
    choice already concrete.
    """
    # call-time import: loading repro.api at module-load time would cycle
    # (api.plan imports this module). Note this pulls the whole facade
    # package on first use, not just the leaf _resolve module — safe,
    # because by call time every module in that chain is importable.
    from ..api import _resolve
    return _resolve.resolve_oracle_backend(backend)


def _cached_loss_term(cache: dict, loss: "GLMLoss", which: str, z, y):
    """Per-round memo of ``loss.grad(z, y)`` / ``loss.hess(z, y)``.

    Keyed on the *identity* of the (possibly traced) response vector ``z``
    — within a round every oracle call sees the same ``z`` object, so
    e.g. repeated ``phvp`` calls in a CG loop reuse one Hessian-diagonal
    evaluation. ``end_round()`` clears the cache, so nothing ever leaks
    across a round boundary (or across traces: a tracer's identity dies
    with its trace, and the cache dies with the round)."""
    if cache.get("z") is not z:
        cache.clear()
        cache["z"] = z
    if which not in cache:
        fn = loss.grad if which == "grad" else loss.hess
        cache[which] = fn(z, y)
    return cache[which]


class OracleBackend:
    """Strategy protocol for the oracle compute path.

    One instance per backend name, resolved ONCE per run (``repro.api``
    resolves the name at plan time; the runtimes bind the implementation
    object at construction) — no per-call string dispatch.  Local-
    placement hooks receive the ``LocalDistERM`` and stacked ``(m, ...)``
    blocks; shard hooks receive the ``ShardedDistERM`` and machine-local
    arrays inside the ``shard_map`` body.  ``pgrad_local``/``phvp_local``
    return the FULL partial gradient / HVP (data term, ``/n``,
    ``lam``-term, block mask) so a backend may fuse the epilogue into
    its kernels.

    ``round_step`` is the whole-round capability: given an algorithm's
    block-local ``update(x, y, g, coeff) -> (x_new, y_new)`` it returns
    a fused one-kernel round step for the cell, or ``None`` when the
    backend (or the cell's channel/shape) cannot rotate the round —
    callers must then compose the round from the oracles above.

    ``response_pair`` says whether the backend offers the two-vector
    response ``response_pair_local``/``response_pair_shard``: machine
    j's unreduced A_j u_j and A_j v_j from one pass over A_j, each
    bit-identical to ``response_*`` on that vector alone.  The folded
    first-order round (``core/algorithms/_fused.py``) needs it.
    """

    name: str = ""
    response_pair: bool = False

    # ---- local placement: blocks stacked on a leading (m, ...) axis ----
    def response_local(self, dist, w_stk):
        raise NotImplementedError

    def pgrad_local(self, dist, w_stk, lgrad):
        raise NotImplementedError

    def phvp_local(self, dist, v_stk, h, av):
        raise NotImplementedError

    # ---- sharded placement: machine-local arrays inside shard_map ----
    def response_shard(self, dist, w_loc):
        raise NotImplementedError

    def pgrad_shard(self, dist, w_loc, lgrad):
        raise NotImplementedError

    def phvp_shard(self, dist, v_loc, h, av):
        raise NotImplementedError

    # ---- two-vector response capability ----
    def response_pair_local(self, dist, u_stk, v_stk):
        raise NotImplementedError

    def response_pair_shard(self, dist, u_loc, v_loc):
        raise NotImplementedError

    # ---- whole-round capability ----
    def round_step(self, dist, update):
        return None


class EinsumBackend(OracleBackend):
    """Plain jnp contractions — XLA schedules them; reference semantics."""

    name = "einsum"

    def response_local(self, dist, w_stk):
        return jnp.einsum("mnd,md->mn", dist.A_stk, w_stk)

    def pgrad_local(self, dist, w_stk, lgrad):
        g = jnp.einsum("mnd,n->md", dist.A_stk, lgrad) / dist.n
        return (g + dist.lam * w_stk) * dist.mask

    def phvp_local(self, dist, v_stk, h, av):
        out = jnp.einsum("mnd,n->md", dist.A_stk, h * av) / dist.n
        return (out + dist.lam * v_stk) * dist.mask

    def response_shard(self, dist, w_loc):
        return dist.A_loc @ w_loc

    def pgrad_shard(self, dist, w_loc, lgrad):
        g = dist.A_loc.T @ lgrad
        return g / dist.n + dist.lam * w_loc

    def phvp_shard(self, dist, v_loc, h, av):
        out = dist.A_loc.T @ (h * av)
        return out / dist.n + dist.lam * v_loc


class KernelBackend(OracleBackend):
    """The Pallas GEMV kernels (a VPU body at one right-hand side, and at
    two for ``feature_matvec``; MXU tiles at more), composed with jnp
    epilogues."""

    name = "kernel"
    response_pair = True

    def response_local(self, dist, w_stk):
        return jax.vmap(kops.feature_matvec)(dist.A_stk, w_stk)

    def response_pair_local(self, dist, u_stk, v_stk):
        z = jax.vmap(kops.feature_matvec)(dist.A_stk,
                                          jnp.stack([u_stk, v_stk], -1))
        return z[..., 0], z[..., 1]

    def response_pair_shard(self, dist, u_loc, v_loc):
        z = kops.feature_matvec(dist.A_loc, jnp.stack([u_loc, v_loc], -1))
        return z[:, 0], z[:, 1]

    def pgrad_local(self, dist, w_stk, lgrad):
        g = jax.vmap(kops.feature_rmatvec,
                     in_axes=(0, None))(dist.A_stk, lgrad) / dist.n
        return (g + dist.lam * w_stk) * dist.mask

    def phvp_local(self, dist, v_stk, h, av):
        out = jax.vmap(kops.feature_hvp,
                       in_axes=(0, None, None))(dist.A_stk, h, av) \
            / dist.n
        return (out + dist.lam * v_stk) * dist.mask

    def response_shard(self, dist, w_loc):
        return kops.feature_matvec(dist.A_loc, w_loc)

    def pgrad_shard(self, dist, w_loc, lgrad):
        g = kops.feature_rmatvec(dist.A_loc, lgrad)
        return g / dist.n + dist.lam * w_loc

    def phvp_shard(self, dist, v_loc, h, av):
        out = kops.feature_hvp(dist.A_loc, h, av)
        return out / dist.n + dist.lam * v_loc


class FusedBackend(KernelBackend):
    """Kernel path + epilogue fusion + the whole-round capability.

    Composed oracles route through ``fused_pgrad``/``fused_phvp`` (the
    gradient epilogue folded into the contraction's last block — one
    A-read per oracle; this is what DISCO-F's CG hits every inner
    iteration, where the round's scalar reduces make a whole-round
    rotation impossible), on the stacked blocks and inside
    ``shard_map`` alike (a shard has no padded coordinates: its mask is
    all ones).  ``round_step``
    builds the one-kernel-per-machine round of
    ``kernels.fused_round.make_round_step`` when the cell qualifies.
    """

    name = "fused"

    def pgrad_shard(self, dist, w_loc, lgrad):
        return kops.fused_pgrad(dist.A_loc, lgrad, w_loc,
                                jnp.ones_like(w_loc), n=dist.n,
                                lam=dist.lam)

    def phvp_shard(self, dist, v_loc, h, av):
        return kops.fused_phvp(dist.A_loc, h, av, v_loc,
                               jnp.ones_like(v_loc), n=dist.n,
                               lam=dist.lam)

    def pgrad_local(self, dist, w_stk, lgrad):
        return jax.vmap(
            functools.partial(kops.fused_pgrad, n=dist.n, lam=dist.lam),
            in_axes=(0, None, 0, 0))(dist.A_stk, lgrad, w_stk, dist.mask)

    def phvp_local(self, dist, v_stk, h, av):
        return jax.vmap(
            functools.partial(kops.fused_phvp, n=dist.n, lam=dist.lam),
            in_axes=(0, None, None, 0, 0))(dist.A_stk, h, av, v_stk,
                                           dist.mask)

    def round_step(self, dist, update):
        from ..kernels import fused_round
        chan = dist.comm.channel
        if fused_round.channel_stages(chan) is None:
            return None     # topk (or unresolved) stages stay composed
        if not fused_round.round_step_fits(dist.n, dist.part.d_max):
            return None     # A_j block exceeds one VMEM tile
        return fused_round.make_round_step(
            dist.A_stk, dist.mask, dist.y, dist.loss,
            n=dist.n, lam=dist.lam, update=update, channel=chan)


BACKEND_IMPLS = {
    "einsum": EinsumBackend(),
    "kernel": KernelBackend(),
    "fused": FusedBackend(),
}


class LocalDistERM:
    """m machines simulated on host; blocks stacked: A (m,n,dmax), w (m,dmax).

    ``backend`` selects the oracle compute path ("einsum" | "kernel" |
    "fused" | "auto"/None for the platform default); the resolved name
    binds an ``OracleBackend`` strategy object once, at construction.
    """

    def __init__(self, prob: ERMProblem, part: FeaturePartition,
                 ledger: Optional[CommLedger] = None,
                 backend: Optional[str] = None,
                 channel=None, faults=None):
        self.prob = prob
        self.part = part
        self.comm = LocalCommunicator(part.m, ledger, channel=channel,
                                      faults=faults)
        self.backend = resolve_oracle_backend(backend)
        self.backend_impl: OracleBackend = BACKEND_IMPLS[self.backend]
        # (m, n, dmax) in one program: op by op, the column slices and
        # their padded copies would each hold a full copy of A at once
        self.A_stk = jax.jit(
            lambda A: part.pad_blocks(part.split_columns(A)))(prob.A)
        self.mask = part.mask()                                   # (m,dmax)
        self.n = prob.n
        self.lam = prob.lam
        self.loss: GLMLoss = prob.loss
        self.y = prob.y
        self._round_cache: dict = {}

    # ---- paper oracles --------------------------------------------------
    def zeros_like_w(self):
        return jnp.zeros((self.part.m, self.part.d_max))

    def zeros_like_response(self):
        """Every machine's unreduced response summand, zero: (m, n)."""
        return jnp.zeros((self.part.m, self.n))

    def response(self, w_stk, tag="z=Aw"):
        """z = sum_j A_j w_j : one ReduceAll of an R^n vector."""
        return self.reduce_response(
            self.backend_impl.response_local(self, w_stk), tag=tag)

    def reduce_response(self, zloc_stk, tag="z=Aw", pretransformed=False):
        """The metered ReduceAll of per-machine response summands
        computed earlier (the rotated rounds of ``_fused.py``): the same
        record, pricing and faults as ``response``.  ``pretransformed``
        when a fused round-step already applied the channel in-kernel,
        which skips the redundant second wire transform."""
        return self.comm.reduce_all(zloc_stk, tag=tag,
                                    pretransformed=pretransformed)

    def local_response_pair(self, u_stk, v_stk):
        """Machine j's unreduced A_j u_j and A_j v_j, (m, n) each, from
        one pass over each A_j: local compute, nothing metered (where
        the backend offers ``response_pair``)."""
        return self.backend_impl.response_pair_local(self, u_stk, v_stk)

    def pgrad(self, w_stk, z):
        """f'_j(w) for every j, stacked — local compute only."""
        lgrad = self._loss_term("grad", z)                    # (n,)
        return self.backend_impl.pgrad_local(self, w_stk, lgrad)

    def phvp(self, v_stk, z, av):
        """(f''(w) v)^[j] stacked, given reduced z=Aw and av=Av — local."""
        h = self._loss_term("hess", z)
        return self.backend_impl.phvp_local(self, v_stk, h, av)

    def fused_round_step(self, update):
        """The backend's whole-round fused step for this cell (see
        ``OracleBackend.round_step``), or ``None`` — program builders
        call this and fall back to the composed oracles on ``None``."""
        return self.backend_impl.round_step(self, update)

    def _loss_term(self, which: str, z):
        return _cached_loss_term(self._round_cache, self.loss, which, z,
                                 self.y)

    def dot(self, u_stk, v_stk, tag="dot"):
        u_stk, v_stk = jnp.asarray(u_stk), jnp.asarray(v_stk)
        shape = (self.part.m, self.part.d_max)
        if u_stk.shape != shape or v_stk.shape != shape:
            raise ValueError(
                f"dot expects stacked blocks of shape {shape}; got "
                f"{u_stk.shape} and {v_stk.shape} — a wrong-rank input "
                f"would silently reduce over the wrong axes")
        # one masked contraction: padding coordinates never contribute,
        # even if a caller let nonzero values leak into the pad region
        local = jnp.einsum("md,md->m", u_stk * self.mask, v_stk)
        return self.comm.reduce_scalar(local, tag=tag)

    def value(self, w_stk, z):
        """f(w) given reduced z (needs one scalar reduce for |w|^2)."""
        sq = self.dot(w_stk, w_stk, tag="|w|^2")
        return jnp.sum(self.loss.value(z, self.y)) / self.n + 0.5 * self.lam * sq

    def objective_from(self, w_stk, zloc_stk):
        """The ERM objective f(w) from the machines' unreduced responses
        A_j w_j, for the folded in-scan measure: its sum over machines
        is measurement, not communication, so nothing is metered."""
        return self.loss.full_value(jnp.sum(zloc_stk, axis=0), self.y,
                                    self.gather_w(w_stk), self.lam)

    def end_round(self):
        self._round_cache.clear()
        self.comm.end_round()

    # ---- incremental-family oracles (Definition 3.2) ---------------------
    def sample_row(self, i: int):
        """Machine-local blocks of data row i: a_i^[j], stacked (m, dmax)."""
        return self.A_stk[:, i, :]

    def dot_row(self, a_i, w_stk, tag="a_i.w"):
        """Scalar a_i . w — one ReduceAll of a scalar."""
        local = jnp.einsum("md,md->m", a_i, w_stk)
        return self.comm.reduce_scalar(local, tag=tag)

    def row_grad(self, a_i, zi, i):
        """Component gradient blocks: a_i^[j] * l'(z_i, y_i) (no 1/n)."""
        return a_i * self.loss.grad(zi, self.y[i])

    # ---- conversions ----------------------------------------------------
    def gather_w(self, w_stk) -> jnp.ndarray:
        return self.part.concat_blocks(self.part.unpad_blocks(w_stk))

    def scatter_w(self, w) -> jnp.ndarray:
        return self.part.pad_blocks(self.part.split_vector(w))


class ShardedDistERM:
    """Same oracle surface inside a shard_map body.

    Local arrays: A_loc (n, d_loc), w_loc (d_loc,). All machines see the
    same y. Construct inside the shard_map body with the mesh axis name.
    """

    def __init__(self, A_loc, y, loss: GLMLoss, lam: float, n: int,
                 axis: str = "model", ledger: Optional[CommLedger] = None,
                 backend: Optional[str] = None,
                 channel=None):
        self.A_loc = A_loc
        self.y = y
        self.loss = loss
        self.lam = lam
        self.n = n
        self.comm = ShardMapCommunicator(axis, ledger, channel=channel)
        self.backend = resolve_oracle_backend(backend)
        self.backend_impl: OracleBackend = BACKEND_IMPLS[self.backend]
        self._round_cache: dict = {}

    def zeros_like_w(self):
        return jnp.zeros((self.A_loc.shape[1],))

    def zeros_like_response(self):
        return jnp.zeros((self.n,))

    def response(self, w_loc, tag="z=Aw"):
        return self.reduce_response(
            self.backend_impl.response_shard(self, w_loc), tag=tag)

    def reduce_response(self, zloc, tag="z=Aw"):
        return self.comm.reduce_all(zloc, tag=tag)

    def local_response_pair(self, u_loc, v_loc):
        return self.backend_impl.response_pair_shard(self, u_loc, v_loc)

    def pgrad(self, w_loc, z):
        lgrad = self._loss_term("grad", z)
        return self.backend_impl.pgrad_shard(self, w_loc, lgrad)

    def phvp(self, v_loc, z, av):
        h = self._loss_term("hess", z)
        return self.backend_impl.phvp_shard(self, v_loc, h, av)

    def _loss_term(self, which: str, z):
        return _cached_loss_term(self._round_cache, self.loss, which, z,
                                 self.y)

    def dot(self, u_loc, v_loc, tag="dot"):
        u_loc, v_loc = jnp.asarray(u_loc), jnp.asarray(v_loc)
        if u_loc.ndim != 1 or u_loc.shape != v_loc.shape:
            raise ValueError(
                f"dot expects machine-local blocks of matching 1-D shape; "
                f"got {u_loc.shape} and {v_loc.shape}")
        return self.comm.reduce_scalar(jnp.vdot(u_loc, v_loc), tag=tag)

    def value(self, w_loc, z):
        sq = self.dot(w_loc, w_loc, tag="|w|^2")
        return jnp.sum(self.loss.value(z, self.y)) / self.n + 0.5 * self.lam * sq

    def end_round(self):
        self._round_cache.clear()
        self.comm.end_round()

    def objective(self, w_loc):
        """f(w) from this machine's block, for the in-scan measure."""
        return self.objective_from(w_loc, self.A_loc @ w_loc)

    def objective_from(self, w_loc, zloc):
        """f(w) from this machine's block and its unreduced response
        A_j w_j (the folded round hands it over): its two psums are
        measurement, not communication, so nothing is metered."""
        z = lax.psum(zloc, self.comm.axis)
        sq = lax.psum(jnp.vdot(w_loc, w_loc), self.comm.axis)
        return jnp.sum(self.loss.value(z, self.y)) / self.n \
            + 0.5 * self.lam * sq

    # ---- incremental-family oracles --------------------------------------
    def sample_row(self, i: int):
        return self.A_loc[i, :]

    def dot_row(self, a_i_loc, w_loc, tag="a_i.w"):
        return self.comm.reduce_scalar(jnp.vdot(a_i_loc, w_loc), tag=tag)

    def row_grad(self, a_i_loc, zi, i):
        return a_i_loc * self.loss.grad(zi, self.y[i])


# --------------------------------------------------------------------------
# shard_map driver
# --------------------------------------------------------------------------

def run_sharded(*args, **kwargs):
    """Removed legacy entry point (deprecated in PR 4, retired now).

    Construct a ``repro.api.RunSpec(placement='sharded', ...)`` and
    execute it via ``repro.api.plan()``/``run()`` — the facade resolves
    ``backend``/``engine``/``channel`` through the single capability
    resolver and validates the combination before compiling.  Library
    internals (and non-registry ``algorithm_body`` callables) use the
    private ``_run_sharded`` driver directly.
    """
    raise TypeError(
        "run_sharded(...) with per-call kwargs was removed: construct a "
        "repro.api.RunSpec(placement='sharded') and execute it via "
        "repro.api.plan()/run(); library internals use "
        "repro.core.runtime._run_sharded")


def _mesh_and_data(prob: ERMProblem, mesh: Optional[Mesh], axis: str):
    """(mesh, axis, A, pad): the mesh the run shards over and A laid out
    column-sharded on it.  An A already column-sharded over a one-axis
    mesh is taken as it lies (padded only where the mesh does not
    divide d); any other A is padded as needed and sharded over
    ``mesh``, by default every device."""
    sharding = getattr(prob.A, "sharding", None)
    if mesh is None and isinstance(sharding, NamedSharding) \
            and len(sharding.mesh.axis_names) == 1 \
            and tuple(sharding.spec) == (None, sharding.mesh.axis_names[0]):
        mesh, axis = sharding.mesh, sharding.mesh.axis_names[0]
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (axis,))
    m = mesh.shape[axis]
    pad = (-prob.d) % m
    A = prob.A
    if pad:
        A = jax.jit(lambda A: jnp.pad(A, ((0, 0), (0, pad))),
                    out_shardings=NamedSharding(mesh, P(None, axis)))(A)
    return mesh, axis, A, pad


class ScanSpan(NamedTuple):
    """One scanned segment of a traced ``ShardedProgram``: its step's
    records ``[start, end)`` in the trace-time ledger, rounds and
    round-boundary marks (relative to ``start``), and scan length."""

    start: int
    end: int
    rounds: int
    count: int
    marks: list


class ShardedProgram:
    """An algorithm run under ``shard_map`` with the data matrix
    column-sharded over ``axis``, traced and compiled once.  (Machinery
    behind ``repro.api``'s sharded placement; ``_run_sharded`` runs one
    once.)

    Two driving modes, selected by ``engine``:

    * ``"python"`` — ``algorithm_body(dist, rounds) -> w_loc`` is traced
      as-is: the historical per-round Python loop unrolled into the
      jitted body. Ledger counts are trace-time (ops per traced call),
      i.e. the full per-round stream.
    * ``"scan"`` — ``program_builder(dist, rounds) -> RoundProgram``
      (step-form, see ``core.engine``) is compiled segment-by-segment
      with ``lax.scan`` inside the shard_map body, so the traced program
      is one scan per segment regardless of the round budget.  The scan
      body and its scanned input are ``core.engine``'s ``round_fn`` and
      ``scan_input``; under a round-scheduled channel each step is
      first captured (``trace_segment``) for its rounds, which the
      scanned round index needs.  ``measure(dist, w_loc) -> scalar``
      (scan only) is evaluated after every round under the
      ``repro.gap`` scope, as the local engine's in-scan measure is,
      and the run returns its (K,) series; program and measure pass
      through ``core.engine.fold_measure``, as every runner's do.

    Either way the ledger is the trace-time records of each
    ``ScanSpan`` replayed ``count`` times through
    ``CommLedger.replay_schedule``, as the local engines replay theirs.

    ``backend`` picks the oracle compute path (see
    ``resolve_oracle_backend``).  Calling the program runs it and meters
    the per-round stream into a ledger; its rounds are numbered from 0.
    """

    def __init__(self, prob: ERMProblem, rounds: int, *,
                 algorithm_body: Optional[Callable] = None,
                 program_builder: Optional[Callable] = None,
                 mesh: Optional[Mesh] = None, axis: str = "model",
                 backend: Optional[str] = None, engine: str = "python",
                 channel=None, measure: Optional[Callable] = None):
        from .channel import parse_channel
        from .engine import resolve_engine

        engine = resolve_engine(engine)
        if engine == "scan" and program_builder is None:
            raise ValueError("engine='scan' requires a program_builder "
                             "(step-form RoundProgram factory)")
        if engine == "python" and algorithm_body is None:
            raise ValueError("engine='python' requires an algorithm_body")
        if measure is not None and engine != "scan":
            raise ValueError("an in-run measure needs engine='scan'")
        self.prob, self.rounds = prob, rounds
        self.algorithm_body, self.program_builder = (algorithm_body,
                                                     program_builder)
        self.engine, self.measure = engine, measure
        self.mesh, self.axis, self.A, self.pad = _mesh_and_data(prob, mesh,
                                                                axis)
        self.backend = resolve_oracle_backend(backend)
        self.chan = parse_channel(channel)
        self.scheduled = getattr(self.chan, "scheduled", False)
        # pallas_call has no shard_map varying-axes rule, and lax.scan
        # carries mixing replicated (z, scalars) with sharded (w-block)
        # values defeat the varying-axes typer; both paths opt out of the
        # (purely diagnostic) check.
        self._check_vma = (self.backend not in ("kernel", "fused")
                           and engine != "scan")
        self.ledger = CommLedger()      # the trace-time records
        self.spans = []     # ScanSpans: one per scanned segment, or
                            # the python mode's whole unrolled run
        self.folded = False     # the traced program folds its measure
        self._compiled = None

    def _body(self, A_loc, y):
        led = self.ledger
        dist = ShardedDistERM(A_loc, y, self.prob.loss, self.prob.lam,
                              self.prob.n, axis=self.axis, ledger=led,
                              backend=self.backend, channel=self.chan)
        if self.engine == "python":
            w = self.algorithm_body(dist, self.rounds)
            # the unrolled run is one span, metered as traced
            self.spans.append(ScanSpan(0, len(led.records), led.rounds, 1,
                                       list(led.round_marks)))
            return w
        from .engine import (GAP_SCOPE, fold_measure, round_fn, scan_input,
                             trace_segment)
        program, measure, self.folded = fold_measure(
            self.program_builder(dist, self.rounds), self.measure)
        carry, gaps = program.init, []
        run_base = 0        # global round index of the next segment's start
        for seg in program.segments:
            xs = (jnp.asarray(seg.xs) if seg.xs is not None
                  else jnp.arange(seg.count, dtype=jnp.int32))
            # the scanned round index needs the step's rounds before
            # lax.scan traces it: capture the step first
            rps = (trace_segment(dist, seg, carry).rounds
                   if self.scheduled else 0)
            start, r0, m0 = len(led.records), led.rounds, len(led.round_marks)

            def scan_body(c, x, _fn=round_fn(dist, seg.step, self.scheduled)):
                c, w = _fn(c, x)
                if measure is None:
                    return c, None
                with jax.named_scope(GAP_SCOPE):
                    return c, measure(dist, w)

            carry, out = lax.scan(
                scan_body, carry,
                scan_input(xs, seg.count, run_base, rps, self.scheduled))
            gaps.append(out)
            rps = led.rounds - r0
            self.spans.append(ScanSpan(
                start, len(led.records), rps, seg.count,
                [m - start for m in led.round_marks[m0:]]))
            run_base += rps * seg.count
        w = program.final(carry)
        return w if measure is None else (w, jnp.concatenate(gaps))

    def _fresh(self) -> Callable:
        """A new ``shard_map`` function over ``_body``, with a fresh
        trace-time ledger and spans: JAX serves a function it has traced
        before from its cache, and would meter nothing."""
        self.ledger, self.spans = CommLedger(), []
        out_specs = (P(self.axis) if self.measure is None
                     else (P(self.axis), P()))
        return jax.shard_map(
            lambda A_loc, y: self._body(A_loc, y), mesh=self.mesh,
            in_specs=(P(None, self.axis), P(None)), out_specs=out_specs,
            check_vma=self._check_vma)

    def trace(self):
        """The sharded program's jaxpr, without running it; the
        trace-time ledger and spans are ``self.ledger`` and
        ``self.spans`` (records metered once per scanned segment, NOT
        expanded)."""
        return jax.make_jaxpr(self._fresh())(self.A, self.prob.y)

    def lower(self):
        """The lowered (compilable, unexecuted) sharded computation,
        with its trace-time ledger and spans as ``trace`` leaves them."""
        return jax.jit(self._fresh()).lower(self.A, self.prob.y)

    def __call__(self, ledger: Optional[CommLedger] = None):
        """Run the compiled program (tracing and compiling it on the
        first call); meter its per-round stream into ``ledger``.
        Returns (w, gaps, ledger): the assembled global w (d,) and the
        measure's (K,) series, or None without a measure."""
        with span("repro.runner"):
            if self._compiled is None:
                self._compiled = self.lower().compile()
        with span("repro.run"):
            out = jax.block_until_ready(self._compiled(self.A, self.prob.y))
        w, gaps = (out, None) if self.measure is None else out
        if self.folded:
            from .engine import count_fold
            count_fold(len(self.spans))
        with span("repro.ledger_replay"):
            # each span's trace-time schedule, repeated ``count`` times;
            # scheduled prices are set from round 0, where the compiled
            # channel stages start, so a caller's ledger takes the
            # replay appended as it stands
            led = CommLedger()
            chan = self.chan if self.scheduled else None
            for sp in self.spans:
                led.replay_schedule(self.ledger.records[sp.start:sp.end],
                                    sp.rounds, sp.marks, sp.count,
                                    channel=chan)
            if ledger is not None:
                ledger.replay_schedule(led.records, led.rounds,
                                       led.round_marks, 1)
                led = ledger
        return (w[:self.prob.d] if self.pad else w), gaps, led


def _run_sharded(prob: ERMProblem, algorithm_body: Optional[Callable],
                 rounds: int,
                 mesh: Optional[Mesh] = None, axis: str = "model",
                 ledger: Optional[CommLedger] = None,
                 backend: Optional[str] = None,
                 engine: str = "python",
                 program_builder: Optional[Callable] = None,
                 channel=None):
    """Run an algorithm once under shard_map (``ShardedProgram``).
    Returns the assembled global w (d,) and the per-round ledger
    (``ledger``, or a fresh one)."""
    program = ShardedProgram(prob, rounds, algorithm_body=algorithm_body,
                             program_builder=program_builder, mesh=mesh,
                             axis=axis, backend=backend, engine=engine,
                             channel=channel)
    w, _, led = program(ledger)
    return w, led
