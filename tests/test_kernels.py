"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fused_round, ops, ref
from repro.kernels.feature_matvec import feature_hvp, feature_matvec, \
    feature_rmatvec


TOL = {jnp.float32: dict(atol=2e-4, rtol=2e-4),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


@pytest.mark.parametrize("n,d", [(8, 8), (48, 64), (300, 200), (513, 129),
                                 (1024, 512), (1100, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_feature_matvec_sweep(n, d, dtype):
    k = jax.random.PRNGKey(n * 1000 + d)
    A = jax.random.normal(k, (n, d)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,)).astype(dtype)
    got = ops.feature_matvec(A, w)
    want = ref.feature_matvec_ref(A, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("n,d", [(16, 16), (96, 48), (257, 130),
                                 (1100, 300)])
@pytest.mark.parametrize("nrhs", [1, 3])
def test_feature_rmatvec_sweep(n, d, nrhs):
    k = jax.random.PRNGKey(7)
    A = jax.random.normal(k, (n, d))
    r = jax.random.normal(jax.random.PRNGKey(8), (n, nrhs))
    r = r[:, 0] if nrhs == 1 else r
    got = ops.feature_rmatvec(A, r)
    want = ref.feature_rmatvec_ref(A, r) if nrhs == 1 else A.T @ r
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


# Interpret mode as the chip reads an overhanging block: every read past
# the array's end yields NaN, so a tile that is not masked poisons its sums.
NAN_PAST_END = pltpu.InterpretParams(out_of_bounds_reads="uninitialized",
                                     uninitialized_memory="nan")
SMALL_BLOCKS = dict(block_n=256, block_d=128, interpret=NAN_PAST_END)
N_DIV, LAM = 5000, 0.3


def _ragged_case(name, A, vecs):
    """(kernel on A as it lies, the same kernel on a zero-padded copy of A
    and of its vectors, sliced back, the plain reference)."""
    n, dj = A.shape
    n_pad, dj_pad = -(-n // 256) * 256, -(-dj // 128) * 128

    def rows(x):   # an R^n vector (or panel) padded with zero rows
        return jnp.pad(x, ((0, n_pad - n),) + ((0, 0),) * (x.ndim - 1))

    def cols(x):   # an R^dj vector (or panel) padded with zero rows
        return jnp.pad(x, ((0, dj_pad - dj),) + ((0, 0),) * (x.ndim - 1))

    A_pad = jnp.pad(A, ((0, n_pad - n), (0, dj_pad - dj)))
    w, r, h, mk = vecs["w"], vecs["r"], vecs["h"], vecs["mask"]
    pg = functools.partial(fused_round.fused_pgrad, n=N_DIV, lam=LAM,
                           **SMALL_BLOCKS)
    ph = functools.partial(fused_round.fused_phvp, n=N_DIV, lam=LAM,
                           **SMALL_BLOCKS)
    if name == "feature_matvec":
        fn = functools.partial(feature_matvec, **SMALL_BLOCKS)
        return (fn(A, w), fn(A_pad, cols(w))[:n],
                A @ w if w.ndim == 2 else ref.feature_matvec_ref(A, w))
    if name == "feature_rmatvec":
        fn = functools.partial(feature_rmatvec, **SMALL_BLOCKS)
        return fn(A, r), fn(A_pad, rows(r))[:dj], A.T @ r
    if name == "feature_hvp":
        fn = functools.partial(feature_hvp, **SMALL_BLOCKS)
        return (fn(A, h, r), fn(A_pad, rows(h), rows(r))[:dj],
                ref.feature_hvp_ref(A, h, r))
    if name == "fused_pgrad":
        return (pg(A, r, w, mk), pg(A_pad, rows(r), cols(w), cols(mk))[:dj],
                ref.fused_pgrad_ref(A, r, w, mk, n=N_DIV, lam=LAM))
    return (ph(A, h, r, w, mk),
            ph(A_pad, rows(h), rows(r), cols(w), cols(mk))[:dj],
            ref.fused_phvp_ref(A, h, r, w, mk, n=N_DIV, lam=LAM))


@pytest.mark.parametrize("nrhs", [1, 3])
@pytest.mark.parametrize("n,dj", [(1100, 300), (513, 129), (1100, 100)])
@pytest.mark.parametrize("name", ["feature_matvec", "feature_rmatvec",
                                  "feature_hvp", "fused_pgrad",
                                  "fused_phvp"])
def test_composed_kernel_reads_ragged_a_in_place(name, n, dj, nrhs):
    """A ragged A_j is read unpadded: its last blocks overhang the array,
    where reads give NaN, and the outputs are bit-identical to the same
    kernel on a zero-padded copy (the aligned path, with no mask).  At
    d_j = 100 the column block is all of A_j's 100 columns."""
    ks = jax.random.split(jax.random.PRNGKey(n + dj + nrhs), 5)
    A = jax.random.normal(ks[0], (n, dj))
    panel = () if nrhs == 1 else (nrhs,)
    vecs = dict(w=jax.random.normal(ks[1], (dj,) + panel),
                r=jax.random.normal(ks[2], (n,) + panel),
                h=jax.random.uniform(ks[3], (n,)),
                mask=(jax.random.uniform(ks[4], (dj,)) > 0.2
                      ).astype(jnp.float32))
    got, padded, want = _ragged_case(name, A, vecs)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(padded))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_epsilon_block_is_read_without_a_pad():
    """At the epsilon block (400,000 x 500, not a multiple of the 512
    grid) no pad as large as A_j is traced: A is read in place, and only
    the vectors are padded to the grid."""
    n, dj = 400_000, 500
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    traced = {
        "feature_matvec": jax.make_jaxpr(
            functools.partial(feature_matvec, interpret=False))(
                f32(n, dj), f32(dj)),
        "fused_pgrad": jax.make_jaxpr(
            functools.partial(fused_round.fused_pgrad, n=n, lam=1e-5,
                              interpret=False))(
                f32(n, dj), f32(n), f32(dj), f32(dj)),
    }
    for name, jaxpr in traced.items():
        pads = [eqn.outvars[0].aval.shape for eqn in _eqns(jaxpr.jaxpr)
                if eqn.primitive.name == "pad"]
        assert pads, name          # the vectors are still padded
        assert all(np.prod(s) < n * dj for s in pads), (name, pads)


def _composed_call(name, n, dj):
    """The one ``pallas_call`` equation of kernel ``name`` on an (n, dj)
    A_j at the default blocks, traced for the chip."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    kw = dict(interpret=False)
    pg = dict(n=n, lam=LAM, **kw)
    fn, args = {
        "feature_matvec": (functools.partial(feature_matvec, **kw),
                           (f32(n, dj), f32(dj))),
        "feature_rmatvec": (functools.partial(feature_rmatvec, **kw),
                            (f32(n, dj), f32(n))),
        "feature_hvp": (functools.partial(feature_hvp, **kw),
                        (f32(n, dj), f32(n), f32(n))),
        "fused_pgrad": (functools.partial(fused_round.fused_pgrad, **pg),
                        (f32(n, dj), f32(n), f32(dj), f32(dj))),
        "fused_phvp": (functools.partial(fused_round.fused_phvp, **pg),
                       (f32(n, dj), f32(n), f32(n), f32(dj), f32(dj))),
    }[name]
    calls = [eqn for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0]


@pytest.mark.parametrize("n,dj", [(1024, 512), (1100, 300), (1100, 600),
                                  (400, 300)])
@pytest.mark.parametrize("name", ["feature_matvec", "feature_rmatvec",
                                  "feature_hvp", "fused_pgrad",
                                  "fused_phvp"])
def test_only_ragged_shapes_trace_a_mask(name, n, dj):
    """A kernel traces the edge mask (an iota in its body) only where its
    contraction axis (d_j for ``feature_matvec``, n for the rest) is
    longer than its 512-wide block and not a multiple of it; a shorter
    axis is one block spanning the whole axis, and needs none."""
    extent = dj if name == "feature_matvec" else n
    masked = extent > 512 and extent % 512 != 0
    body = list(_eqns(_composed_call(name, n, dj).params["jaxpr"]))
    assert any(e.primitive.name == "iota" for e in body) == masked


@pytest.mark.parametrize("name", ["feature_matvec", "feature_rmatvec",
                                  "feature_hvp", "fused_pgrad",
                                  "fused_phvp"])
def test_epsilon_block_spans_its_columns(name):
    """At the epsilon block (400,000 x 500) the A tile is 512 rows by all
    500 columns: no block overhangs the lane axis, and the grid runs
    cdiv(400,000, 512) = 782 row blocks."""
    grid = _composed_call(name, 400_000, 500).params["grid_mapping"]
    a_block = grid.block_mappings[0].block_shape
    assert [getattr(b, "block_size", b) for b in a_block] == [512, 500]
    assert 782 in grid.grid and 1 in grid.grid


def test_batched_rhs_matches_loop():
    k = jax.random.PRNGKey(3)
    A = jax.random.normal(k, (64, 40))
    W = jax.random.normal(jax.random.PRNGKey(4), (40, 5))
    got = ops.feature_matvec(A, W)
    for i in range(5):
        np.testing.assert_allclose(got[:, i], A @ W[:, i], atol=2e-4,
                                   rtol=2e-4)


@given(d=st.integers(2, 600), seed=st.integers(0, 50))
@settings(max_examples=25, deadline=None)
def test_tridiag_property(d, seed):
    k = jax.random.PRNGKey(seed)
    diag = jax.random.normal(k, (d,))
    off = jax.random.normal(jax.random.PRNGKey(seed + 1), (d - 1,))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (d,))
    got = ops.tridiag_matvec(diag, off, v)
    want = ref.tridiag_matvec_ref(diag, off, v)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_tridiag_identity_and_shift():
    d = 300
    v = jax.random.normal(jax.random.PRNGKey(0), (d,))
    # identity
    got = ops.tridiag_matvec(jnp.ones(d), jnp.zeros(d - 1), v)
    np.testing.assert_allclose(got, v, atol=1e-6)
    # pure shift structure: diag=0, off=1 -> out[k] = v[k-1] + v[k+1]
    got = ops.tridiag_matvec(jnp.zeros(d), jnp.ones(d - 1), v)
    want = jnp.zeros(d).at[:-1].add(v[1:]).at[1:].add(v[:-1])
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("t,k,d", [(5, 1, 16), (37, 4, 96), (256, 8, 64)])
def test_moe_combine_sweep(t, k, d):
    key = jax.random.PRNGKey(t)
    x = jax.random.normal(key, (t, k, d))
    w = jax.random.normal(jax.random.PRNGKey(k), (t, k))
    got = ops.moe_combine(x, w)
    want = ref.moe_combine_ref(x, w)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_kernels_used_by_erm_path():
    """ops wrappers compute the ERM round quantities correctly."""
    from repro.core import make_random_erm
    from repro.core.partition import even_partition
    prob = make_random_erm(n=40, d=32, seed=0)
    part = even_partition(32, 4)
    w = jax.random.normal(jax.random.PRNGKey(5), (32,))
    wjs = part.split_vector(w)
    Ajs = part.split_columns(prob.A)
    z = sum(ops.feature_matvec(Aj, wj) for Aj, wj in zip(Ajs, wjs))
    np.testing.assert_allclose(z, prob.A @ w, atol=1e-4, rtol=1e-4)
