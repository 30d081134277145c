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


# The B = 1 body's A_j^T tile at the epsilon block: all 500 rows of
# A_j^T (rounded up to 504, whole 8-row tiles) by the 128-lane columns of
# n that fit 8 MiB, and the MXU body's A_j tile: 512 rows by all 500
# columns.
EPS_N, EPS_DJ = 400_000, 500
GEMV_EPS_TILE, GEMV_EPS_STEPS = [504, 4096], 98


@pytest.mark.parametrize("b", [1, 3])
def test_epsilon_block_is_read_without_a_pad(b):
    """At the epsilon block (400,000 x 500, not a multiple of the 512
    grid) no pad as large as A_j is traced: A is read in place.  The MXU
    body (B = 3) pads only the vectors to its grid; the B = 1 body pads
    nothing, and no R^n vector takes a 128-lane panel."""
    n, dj = EPS_N, EPS_DJ
    for name in ("feature_matvec", "fused_pgrad"):
        jaxpr = _composed_jaxpr(name, n, dj, b)
        pads = [eqn.outvars[0].aval.shape for eqn in _eqns(jaxpr.jaxpr)
                if eqn.primitive.name == "pad"]
        if b > 1:
            assert pads, name          # the vectors are still padded
            assert all(np.prod(s) < n * dj for s in pads), (name, pads)
            continue
        assert not pads, (name, pads)
        panels = [v.aval.shape for eqn in _eqns(jaxpr.jaxpr)
                  for v in eqn.outvars
                  if len(v.aval.shape) == 2 and max(v.aval.shape) >= n
                  and min(v.aval.shape) > 1]
        assert panels == [(dj, n)], (name, panels)   # A_j^T itself


def _composed_jaxpr(name, n, dj, b=1):
    """Kernel ``name`` on an (n, dj) A_j at the default blocks, traced for
    the chip, with 1-D vectors (B = 1) or width-``b`` panels."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    panel = () if b == 1 else (b,)
    kw = dict(interpret=False)
    pg = dict(n=n, lam=LAM, **kw)
    fn, args = {
        "feature_matvec": (functools.partial(feature_matvec, **kw),
                           (f32(n, dj), f32(dj, *panel))),
        "feature_rmatvec": (functools.partial(feature_rmatvec, **kw),
                            (f32(n, dj), f32(n, *panel))),
        "feature_hvp": (functools.partial(feature_hvp, **kw),
                        (f32(n, dj), f32(n), f32(n, *panel))),
        "fused_pgrad": (functools.partial(fused_round.fused_pgrad, **pg),
                        (f32(n, dj), f32(n, *panel), f32(dj, *panel),
                         f32(dj))),
        "fused_phvp": (functools.partial(fused_round.fused_phvp, **pg),
                       (f32(n, dj), f32(n), f32(n, *panel),
                        f32(dj, *panel), f32(dj))),
    }[name]
    return jax.make_jaxpr(fn)(*args)


def _composed_call(name, n, dj, b=1):
    """The one ``pallas_call`` equation of ``_composed_jaxpr``."""
    calls = [eqn for eqn in _eqns(_composed_jaxpr(name, n, dj, b).jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0]


NAMES = ["feature_matvec", "feature_rmatvec", "feature_hvp", "fused_pgrad",
         "fused_phvp"]


@pytest.mark.parametrize("b", [1, 3], ids=["b1", "b3"])
@pytest.mark.parametrize("n,dj", [(1024, 512), (1100, 300), (1100, 600),
                                  (400, 300)])
@pytest.mark.parametrize("name", NAMES)
def test_only_ragged_shapes_trace_a_mask(name, n, dj, b):
    """A kernel traces the edge mask (an iota in its body) only where its
    contraction axis (d_j for ``feature_matvec``, n for the rest) ends in
    a cut block.  The MXU body (B = 3) masks where the axis is longer
    than its 512-wide block and not a multiple of it; a shorter axis is
    one block spanning the whole axis, and needs none.  The B = 1 body
    skips whole row chunks and lane groups past the end, so it masks
    where the axis is not a multiple of one chunk: 8 rows of A_j^T (d_j)
    or 128 lanes (n)."""
    extent = dj if name == "feature_matvec" else n
    if b > 1:
        masked = extent > 512 and extent % 512 != 0
    else:
        masked = extent % (8 if name == "feature_matvec" else 128) != 0
    body = list(_eqns(_composed_call(name, n, dj, b).params["jaxpr"]))
    assert any(e.primitive.name == "iota" for e in body) == masked


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("name", NAMES)
def test_epsilon_block_spans_its_columns(name, b):
    """At the epsilon block (400,000 x 500) the MXU body's A tile (B = 3)
    is 512 rows by all 500 columns: no block overhangs the lane axis, and
    the grid runs cdiv(400,000, 512) = 782 row blocks.  The B = 1 body's
    tile of A_j^T is all 500 rows (as 504) by 4,096 columns of n: 98 grid
    steps over n, one over d_j and one over the right-hand side."""
    grid = _composed_call(name, EPS_N, EPS_DJ, b).params["grid_mapping"]
    a_block = grid.block_mappings[0].block_shape
    got = [getattr(x, "block_size", x) for x in a_block]
    if b > 1:
        assert got == [512, 500]
        assert 782 in grid.grid and 1 in grid.grid
    else:
        assert got == GEMV_EPS_TILE
        assert sorted(grid.grid) == [1, 1, GEMV_EPS_STEPS]


def _gemv_case(name, A, vecs, **kw):
    """Kernel ``name`` vmapped over the machine axis of A as the local
    oracles call it, and its float64 product."""
    f64 = lambda x: np.asarray(x, np.float64)                  # noqa: E731
    A64 = f64(A)
    w, r, h, mk = (f64(vecs[k]) for k in ("w", "r", "h", "mask"))
    at_r = np.einsum("mnd,n->md", A64, r)
    at_hr = np.einsum("mnd,n->md", A64, h * r)
    pg = dict(n=N_DIV, lam=LAM, **kw)
    if name == "feature_matvec":
        fn = jax.vmap(functools.partial(feature_matvec, **kw))
        return fn(A, vecs["w"]), np.einsum("mnd,md->mn", A64, w)
    if name == "feature_rmatvec":
        fn = jax.vmap(functools.partial(feature_rmatvec, **kw),
                      in_axes=(0, None))
        return fn(A, vecs["r"]), at_r
    if name == "feature_hvp":
        fn = jax.vmap(functools.partial(feature_hvp, **kw),
                      in_axes=(0, None, None))
        return fn(A, vecs["h"], vecs["r"]), at_hr
    if name == "fused_pgrad":
        fn = jax.vmap(functools.partial(fused_round.fused_pgrad, **pg),
                      in_axes=(0, None, 0, 0))
        return (fn(A, vecs["r"], vecs["w"], vecs["mask"]),
                (at_r / N_DIV + LAM * w) * mk)
    fn = jax.vmap(functools.partial(fused_round.fused_phvp, **pg),
                  in_axes=(0, None, None, 0, 0))
    return (fn(A, vecs["h"], vecs["r"], vecs["w"], vecs["mask"]),
            (at_hr / N_DIV + LAM * w) * mk)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,dj", [(1100, 129), (2100, 289), (2500, 500)])
@pytest.mark.parametrize("name", NAMES)
def test_gemv_body_matches_float64(name, n, dj, dtype):
    """The B = 1 body at its default tiles, vmapped over 4 machines as the
    local oracles call it, against a float64 product of the same inputs:
    d_j not a multiple of 8 (a masked last row chunk) and n not a
    multiple of 128 (a masked last lane group) in one tile that overhangs
    A_j^T, where reads give NaN.  (Several steps along either axis, at
    small blocks: ``test_composed_kernel_reads_ragged_a_in_place``.)"""
    ks = jax.random.split(jax.random.PRNGKey(n + dj), 5)
    m = 4
    A = jax.random.normal(ks[0], (m, n, dj)).astype(dtype)
    vecs = dict(w=jax.random.normal(ks[1], (m, dj)).astype(dtype),
                r=jax.random.normal(ks[2], (n,)).astype(dtype),
                h=jax.random.uniform(ks[3], (n,)).astype(dtype),
                mask=(jax.random.uniform(ks[4], (m, dj)) > 0.2
                      ).astype(dtype))
    got, want = _gemv_case(name, A, vecs, interpret=NAN_PAST_END)
    assert got.shape == want.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               **TOL[dtype])


@pytest.mark.parametrize("name", NAMES)
def test_gemv_and_mxu_bodies_agree(name):
    """Each column of a B = 3 panel (the MXU body) equals the B = 1 body
    on that column alone, to f32 rounding: one algorithm at two widths."""
    n, dj, b = 1100, 300, 3
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    A = jax.random.normal(ks[0], (n, dj))
    w = jax.random.normal(ks[1], (dj, b))
    r = jax.random.normal(ks[2], (n, b))
    h = jax.random.uniform(ks[3], (n,))
    mk = (jax.random.uniform(ks[4], (dj,)) > 0.2).astype(jnp.float32)
    pg = functools.partial(fused_round.fused_pgrad, n=N_DIV, lam=LAM)
    ph = functools.partial(fused_round.fused_phvp, n=N_DIV, lam=LAM)
    fn = {"feature_matvec": lambda w, r: feature_matvec(A, w),
          "feature_rmatvec": lambda w, r: feature_rmatvec(A, r),
          "feature_hvp": lambda w, r: feature_hvp(A, h, r),
          "fused_pgrad": lambda w, r: pg(A, r, w, mk),
          "fused_phvp": lambda w, r: ph(A, h, r, w, mk)}[name]
    panel = np.asarray(fn(w, r))
    for col in range(b):
        one = np.asarray(fn(w[:, col:col + 1], r[:, col:col + 1]))
        assert one.shape == panel[:, :1].shape
        np.testing.assert_allclose(
            one[:, 0], panel[:, col], rtol=1e-5,
            atol=1e-5 * np.abs(panel[:, col]).max())


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
