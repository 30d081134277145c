"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode accepts block shapes and casts that the chip's compiler
(Mosaic) refuses, so these tests compile each kernel with
``interpret=False`` for a described ``v5e:2x2`` topology: nothing runs,
but whatever the chip would refuse fails here.  The topology is
described inside a module fixture (never at import), and every case
skips from there when this installation cannot describe it.
"""
from __future__ import annotations

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.algorithms.dagd import dagd_program
from repro.core.algorithms.dgd import dgd_program
from repro.core.algorithms.prox_dagd import prox_dagd_program, \
    soft_threshold
from repro.core.erm import make_random_erm
from repro.core.partition import even_partition
from repro.core.runtime import LocalDistERM
from repro.kernels import fused_round
from repro.kernels.feature_matvec import BLOCK_D, BLOCK_N, feature_hvp, \
    feature_matvec, feature_rmatvec


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described-topology compile can be written to the persistent
    # cache but never read back without a chip; keep the cache out of it.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no libtpu, or it cannot describe v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# The admitted tile limit: a 512 x 512 A_j block per machine.
N_TILE, D_TILE = BLOCK_N, BLOCK_D

PROGRAMS = {
    "dgd": lambda dist, lam: dgd_program(dist, 3, L=1.0, lam=lam),
    "dagd": lambda dist, lam: dagd_program(dist, 3, L=1.0, lam=lam),
    # lam = 0: the FISTA branch, whose momentum enters as a scanned coeff
    "prox_dagd": lambda dist, lam: prox_dagd_program(
        dist, 3, L=1.0, prox=soft_threshold(1e-3), lam=0.0),
}


def _round_step(monkeypatch, algo: str, m: int, channel: str):
    """The fused step the real program builder makes for this cell,
    built for the chip (``interpret=False``) instead of this host."""
    built = []

    def make(*args, **kwargs):
        step = real(*args, **dict(kwargs, interpret=False))
        built.append(step)
        return step

    real = fused_round.make_round_step
    monkeypatch.setattr(fused_round, "make_round_step", make)
    prob = make_random_erm(n=N_TILE, d=D_TILE * m, loss="logistic",
                           lam=1e-2, seed=0)
    dist = LocalDistERM(prob, even_partition(prob.d, m), backend="fused",
                        channel=channel)
    PROGRAMS[algo](dist, prob.lam)
    assert len(built) == 1, "the cell did not take the whole-round kernel"
    return built[0], prob.n, dist.part.d_max


def _step_args(one_chip, n, m, d_max, batch=()):
    return (_shape(one_chip, batch + (n,)),
            _shape(one_chip, batch + (m, d_max)),
            _shape(one_chip, batch + (m, d_max)),
            _shape(one_chip, ()),
            _shape(one_chip, (), jnp.int32))


@pytest.mark.parametrize("channel", ["identity", "bf16", "int8", "fp16",
                                     "sched:int8@0,fp16@5"])
@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("algo", sorted(PROGRAMS))
def test_round_step_compiles(one_chip, monkeypatch, algo, m, channel):
    step, n, d_max = _round_step(monkeypatch, algo, m, channel)
    compiled = jax.jit(step).lower(
        *_step_args(one_chip, n, m, d_max)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_round_step_compiles_under_vmap(one_chip, monkeypatch):
    """``execute_batch`` and the service vmap grouped cells through the
    step: the batched kernel must compile too."""
    m = 4
    step, n, d_max = _round_step(monkeypatch, "dagd", m, "int8")
    batched = jax.vmap(step, in_axes=(0, 0, 0, None, None))
    compiled = jax.jit(batched).lower(
        *_step_args(one_chip, n, m, d_max, batch=(3,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The LIBSVM epsilon dataset split over four machines: 400,000 x 500
# per A_j block, the composed oracles' shape when a block exceeds a tile.
EPS_N, EPS_DJ = 400_000, 500

COMPOSED = {
    "feature_matvec": (
        lambda A, w: feature_matvec(A, w, interpret=False),
        [(EPS_N, EPS_DJ), (EPS_DJ,)]),
    "fused_pgrad": (
        functools.partial(fused_round.fused_pgrad, n=EPS_N, lam=1e-5,
                          interpret=False),
        [(EPS_N, EPS_DJ), (EPS_N,), (EPS_DJ,), (EPS_DJ,)]),
    "fused_phvp": (
        functools.partial(fused_round.fused_phvp, n=EPS_N, lam=1e-5,
                          interpret=False),
        [(EPS_N, EPS_DJ), (EPS_N,), (EPS_N,), (EPS_DJ,), (EPS_DJ,)]),
}


@pytest.mark.parametrize("name", sorted(COMPOSED))
def test_composed_oracle_compiles_at_epsilon_block(one_chip, name):
    fn, shapes = COMPOSED[name]
    compiled = jax.jit(fn).lower(
        *[_shape(one_chip, s) for s in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The B = 1 (VPU) body of every composed kernel at a deployment's block:
# epsilon's 400,000 x 500 and PASCAL ocr's 3,500,000 x 289 shard.  A
# 2-D A_j is kept with n as its minor dimension, which is A_j^T in
# row-major tiles, so the body reads it with no copy.
GEMV_BLOCKS = {"epsilon": (EPS_N, EPS_DJ), "ocr": (3_500_000, 289)}


def _gemv_call(name, n, dj):
    pg = dict(n=n, lam=1e-5, interpret=False)
    return {
        "feature_matvec": (lambda A, w: feature_matvec(A, w, interpret=False),
                           [(n, dj), (dj,)]),
        "feature_rmatvec": (lambda A, r: feature_rmatvec(A, r,
                                                         interpret=False),
                            [(n, dj), (n,)]),
        "feature_hvp": (lambda A, h, av: feature_hvp(A, h, av,
                                                     interpret=False),
                        [(n, dj), (n,), (n,)]),
        "fused_pgrad": (functools.partial(fused_round.fused_pgrad, **pg),
                        [(n, dj), (n,), (dj,), (dj,)]),
        "fused_phvp": (functools.partial(fused_round.fused_phvp, **pg),
                       [(n, dj), (n,), (n,), (dj,), (dj,)]),
    }[name]


@pytest.mark.parametrize("block", sorted(GEMV_BLOCKS))
@pytest.mark.parametrize("name", ["feature_matvec", "feature_rmatvec",
                                  "feature_hvp", "fused_pgrad",
                                  "fused_phvp"])
def test_gemv_body_compiles_at_deployment_blocks(one_chip, name, block):
    n, dj = GEMV_BLOCKS[block]
    fn, shapes = _gemv_call(name, n, dj)
    compiled = jax.jit(fn).lower(
        *[_shape(one_chip, s) for s in shapes]).compile()
    text = compiled.as_text()
    assert name in text and "tpu_custom_call" in text
    # no copy of A_j, and no 128-lane panel of an R^n vector
    assert compiled.memory_analysis().temp_size_in_bytes < n * 4 * 8


# Every composed kernel, at a block a little wider than one tile.
NAMED_N, NAMED_DJ = 4096, 500
NAMED = {
    "feature_matvec": (lambda A, w: feature_matvec(A, w, interpret=False),
                       [(NAMED_N, NAMED_DJ), (NAMED_DJ,)]),
    "feature_rmatvec": (lambda A, r: feature_rmatvec(A, r, interpret=False),
                        [(NAMED_N, NAMED_DJ), (NAMED_N,)]),
    "feature_hvp": (lambda A, h, av: feature_hvp(A, h, av, interpret=False),
                    [(NAMED_N, NAMED_DJ), (NAMED_N,), (NAMED_N,)]),
    "fused_pgrad": (
        functools.partial(fused_round.fused_pgrad, n=NAMED_N, lam=1e-5,
                          interpret=False),
        [(NAMED_N, NAMED_DJ), (NAMED_N,), (NAMED_DJ,), (NAMED_DJ,)]),
    "fused_phvp": (
        functools.partial(fused_round.fused_phvp, n=NAMED_N, lam=1e-5,
                          interpret=False),
        [(NAMED_N, NAMED_DJ), (NAMED_N,), (NAMED_N,), (NAMED_DJ,),
         (NAMED_DJ,)]),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_composed_kernels_carry_stable_names(one_chip, name):
    """Each composed kernel is named for its entry point: the lowered
    module's custom call carries ``kernel_name``, and the compiled
    instruction, whose name labels the kernel's events in a device trace,
    holds it too (vmapped over machines inside a jit, as the oracles call
    it).  The benchmark's roofline readers select on these names."""
    fn, shapes = NAMED[name]
    lowered = jax.jit(jax.vmap(jax.jit(fn))).lower(
        *[_shape(one_chip, (4,) + s) for s in shapes])
    assert f'kernel_name = "{name}"' in lowered.as_text()
    calls = [line.split("=", 1)[0] for line in
             lowered.compile().as_text().splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert calls and all(name in call for call in calls), calls
