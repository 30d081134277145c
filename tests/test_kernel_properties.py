"""Property-based kernel-vs-reference parity for the feature kernels.

Sweeps ragged shapes (deliberately not multiples of the 128-lane MXU
tile), dtypes (f32 / bf16), and RHS batch widths including B > 128 (which
exercises the batch-axis grid tiling) through ``feature_matvec`` /
``feature_rmatvec`` / ``feature_hvp`` against the pure-jnp oracles in
``kernels/ref.py``. Uses hypothesis when installed; otherwise the
deterministic fallback shim in ``tests/_hypothesis_fallback.py`` replays
a fixed spread of examples (range endpoints + seeded fills), so CI runs
are reproducible either way.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

def _tol(dtype, k):
    """Tolerance for a length-k contraction: bf16 inputs carry ~2^-8
    relative noise per term, so absolute error grows like sqrt(k)."""
    if dtype == jnp.bfloat16:
        return dict(atol=6e-3 * max(1.0, k) ** 0.5, rtol=3e-2)
    return dict(atol=2e-4, rtol=2e-4)

# endpoints sit on ragged, off-tile sizes on purpose
N_RANGE = (3, 290)
D_RANGE = (2, 261)
BATCHES = (1, 2, 130)          # 130 > BLOCK_B exercises the batch grid


def _mats(n, d, b, dtype, seed):
    ka, kb, kh = jax.random.split(jax.random.PRNGKey(seed), 3)
    A = jax.random.normal(ka, (n, d)).astype(dtype)
    rhs_d = jax.random.normal(kb, (d, b)).astype(dtype)
    rhs_n = jax.random.normal(kb, (n, b)).astype(dtype)
    # h plays l''(z): positive and O(1), like a GLM curvature
    h = jax.nn.sigmoid(jax.random.normal(kh, (n,))).astype(dtype)
    if b == 1:
        rhs_d, rhs_n = rhs_d[:, 0], rhs_n[:, 0]
    return A, rhs_d, rhs_n, h


def _check(got, want, dtype, contraction):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **_tol(dtype, contraction))


@given(n=st.integers(*N_RANGE), d=st.integers(*D_RANGE),
       b=st.sampled_from(BATCHES),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 99))
@settings(max_examples=6, deadline=None)
def test_feature_matvec_property(n, d, b, dtype, seed):
    A, w, _, _ = _mats(n, d, b, dtype, seed)
    got = ops.feature_matvec(A, w)
    want = ref.feature_matvec_ref(A, w) if b == 1 else A @ w
    assert got.shape == want.shape and got.dtype == A.dtype
    _check(got, want, dtype, contraction=d)


@given(n=st.integers(*N_RANGE), d=st.integers(*D_RANGE),
       b=st.sampled_from(BATCHES),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 99))
@settings(max_examples=6, deadline=None)
def test_feature_rmatvec_property(n, d, b, dtype, seed):
    A, _, r, _ = _mats(n, d, b, dtype, seed)
    got = ops.feature_rmatvec(A, r)
    want = ref.feature_rmatvec_ref(A, r) if b == 1 else A.T @ r
    assert got.shape == want.shape and got.dtype == A.dtype
    _check(got, want, dtype, contraction=n)


@given(n=st.integers(*N_RANGE), d=st.integers(*D_RANGE),
       b=st.sampled_from(BATCHES),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 99))
@settings(max_examples=6, deadline=None)
def test_feature_hvp_property(n, d, b, dtype, seed):
    A, _, av, h = _mats(n, d, b, dtype, seed)
    got = ops.feature_hvp(A, h, av)
    want = ref.feature_hvp_ref(A, h, av)
    assert got.shape == want.shape and got.dtype == A.dtype
    _check(got, want, dtype, contraction=n)
    # escape hatch returns the oracle itself (compiled like the op: an
    # eager bf16 oracle rounds its fused product differently)
    np.testing.assert_array_equal(
        np.asarray(ops.feature_hvp(A, h, av, use_kernel=False), np.float32),
        np.asarray(jax.jit(ref.feature_hvp_ref)(A, h, av), np.float32))


def test_hvp_is_fused_rmatvec():
    """feature_hvp(A, h, av) == feature_rmatvec(A, h * av): the fusion
    must not change the math, only where the Hadamard happens."""
    k = jax.random.PRNGKey(0)
    A = jax.random.normal(k, (130, 67))
    h = jax.random.normal(jax.random.PRNGKey(1), (130,)) ** 2
    av = jax.random.normal(jax.random.PRNGKey(2), (130, 5))
    got = ops.feature_hvp(A, h, av)
    want = ops.feature_rmatvec(A, h[:, None] * av)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_wide_batch_tiling_matches_column_slices():
    """B > BLOCK_B: each 128-wide batch tile must reproduce the per-column
    GEMV (regression for the formerly unused bb tiling)."""
    k = jax.random.PRNGKey(3)
    n, d, B = 96, 70, 200
    A = jax.random.normal(k, (n, d))
    W = jax.random.normal(jax.random.PRNGKey(4), (d, B))
    R = jax.random.normal(jax.random.PRNGKey(5), (n, B))
    zs = ops.feature_matvec(A, W)
    gs = ops.feature_rmatvec(A, R)
    assert zs.shape == (n, B) and gs.shape == (d, B)
    for i in (0, 127, 128, B - 1):    # straddle the batch-block boundary
        np.testing.assert_allclose(zs[:, i], A @ W[:, i],
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(gs[:, i], A.T @ R[:, i],
                                   atol=2e-4, rtol=2e-4)


@given(n=st.integers(*N_RANGE), d=st.integers(*D_RANGE),
       b=st.sampled_from(BATCHES),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 99))
@settings(max_examples=6, deadline=None)
def test_fused_pgrad_property(n, d, b, dtype, seed):
    """fused_pgrad == (A^T r / n + lam w) * mask: one accumulation pass
    with the gradient epilogue applied on the last grid step, across
    ragged shapes, bf16, and B > BLOCK_B."""
    from repro.kernels.fused_round import fused_pgrad
    A, w, r, _ = _mats(n, d, b, dtype, seed)
    lam = 0.03
    mask = (jnp.arange(d) % 5 != 3).astype(jnp.float32)
    got = fused_pgrad(A, r, w, mask, n=n, lam=lam)
    rf, wf = [np.asarray(x, np.float32) for x in (r, w)]
    want = (np.asarray(A, np.float32).T @ rf / n + lam * wf) \
        * (np.asarray(mask)[:, None] if b > 1 else np.asarray(mask))
    assert got.shape == want.shape
    _check(got, want, dtype, contraction=n)


@given(n=st.integers(*N_RANGE), d=st.integers(*D_RANGE),
       b=st.sampled_from(BATCHES),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 99))
@settings(max_examples=6, deadline=None)
def test_fused_phvp_property(n, d, b, dtype, seed):
    """fused_phvp == (A^T (h . av) / n + lam v) * mask: the Hadamard,
    the contraction, and the HVP epilogue in a single pass."""
    from repro.kernels.fused_round import fused_phvp
    A, v, av, h = _mats(n, d, b, dtype, seed)
    lam = 0.03
    mask = (jnp.arange(d) % 7 != 2).astype(jnp.float32)
    got = fused_phvp(A, h, av, v, mask, n=n, lam=lam)
    hf = np.asarray(h, np.float32)
    avf, vf = [np.asarray(x, np.float32) for x in (av, v)]
    had = hf[:, None] * avf if b > 1 else hf * avf
    want = (np.asarray(A, np.float32).T @ had / n + lam * vf) \
        * (np.asarray(mask)[:, None] if b > 1 else np.asarray(mask))
    assert got.shape == want.shape
    _check(got, want, dtype, contraction=n)


@pytest.mark.parametrize("block_b", [128, 256])
def test_explicit_batch_block_override(block_b):
    """block_b is a real tiling knob: any legal setting is exact."""
    from repro.kernels.feature_matvec import feature_matvec, feature_hvp
    k = jax.random.PRNGKey(6)
    A = jax.random.normal(k, (64, 48))
    W = jax.random.normal(jax.random.PRNGKey(7), (48, 300))
    got = feature_matvec(A, W, block_b=block_b)
    np.testing.assert_allclose(got, A @ W, atol=2e-4, rtol=2e-4)
    h = jax.random.normal(jax.random.PRNGKey(8), (64,)) ** 2
    R = jax.random.normal(jax.random.PRNGKey(9), (64, 300))
    got = feature_hvp(A, h, R, block_b=block_b)
    np.testing.assert_allclose(got, A.T @ (h[:, None] * R),
                               atol=2e-4, rtol=2e-4)
