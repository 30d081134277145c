"""ERM oracles: partial gradients/HVPs assemble to the full ones —
the identity that makes one R^n ReduceAll per round sufficient."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

# `hypothesis` may be absent: tests/conftest.py installs the deterministic
# fallback (tests/_hypothesis_fallback.py) before collection, so this
# import — and every other property-test module — collects cleanly.
from hypothesis import given, settings, strategies as st

from repro.core.erm import LOSSES, make_random_erm
from repro.core.partition import even_partition


@pytest.mark.parametrize("loss", ["squared", "logistic", "squared_hinge"])
def test_gradient_matches_autodiff(loss):
    prob = make_random_erm(n=20, d=15, loss=loss, lam=0.1, seed=0)
    w = jax.random.normal(jax.random.PRNGKey(1), (15,))
    g_manual = prob.gradient(w)
    g_auto = jax.grad(prob.value)(w)
    np.testing.assert_allclose(g_manual, g_auto, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("loss", ["squared", "logistic"])
def test_hvp_matches_autodiff(loss):
    prob = make_random_erm(n=20, d=15, loss=loss, lam=0.1, seed=0)
    w = jax.random.normal(jax.random.PRNGKey(1), (15,))
    v = jax.random.normal(jax.random.PRNGKey(2), (15,))
    hvp_auto = jax.jvp(jax.grad(prob.value), (w,), (v,))[1]
    np.testing.assert_allclose(prob.hvp(w, v), hvp_auto, atol=1e-5,
                               rtol=1e-5)


@given(m=st.integers(1, 6), seed=st.integers(0, 20))
@settings(max_examples=20, deadline=None)
def test_partial_gradients_assemble(m, seed):
    prob = make_random_erm(n=12, d=18, loss="logistic", lam=0.05, seed=seed)
    part = even_partition(18, m)
    w = jax.random.normal(jax.random.PRNGKey(seed), (18,))
    wjs = part.split_vector(w)
    Ajs = part.split_columns(prob.A)
    # the single ReduceAll quantity
    z = sum(prob.local_response(Aj, wj) for Aj, wj in zip(Ajs, wjs))
    np.testing.assert_allclose(z, prob.A @ w, atol=1e-5, rtol=1e-5)
    g_parts = [prob.partial_gradient(Aj, wj, z)
               for Aj, wj in zip(Ajs, wjs)]
    np.testing.assert_allclose(part.concat_blocks(g_parts),
                               prob.gradient(w), atol=1e-5, rtol=1e-5)


def test_partial_hvp_assembles():
    prob = make_random_erm(n=14, d=10, loss="squared", lam=0.2, seed=3)
    part = even_partition(10, 3)
    w = jax.random.normal(jax.random.PRNGKey(0), (10,))
    v = jax.random.normal(jax.random.PRNGKey(1), (10,))
    Ajs = part.split_columns(prob.A)
    wjs, vjs = part.split_vector(w), part.split_vector(v)
    z = prob.A @ w
    av = prob.A @ v
    parts = [prob.partial_hvp(Aj, vj, z, av) for Aj, vj in zip(Ajs, vjs)]
    np.testing.assert_allclose(part.concat_blocks(parts), prob.hvp(w, v),
                               atol=1e-5, rtol=1e-5)


def test_smoothness_bound_is_upper_bound():
    prob = make_random_erm(n=30, d=20, loss="squared", lam=0.1, seed=0)
    H = np.asarray(prob.A.T @ prob.A) / prob.n + prob.lam * np.eye(20)
    lmax = float(np.linalg.eigvalsh(H).max())
    assert prob.smoothness_bound() >= lmax - 1e-6


@pytest.mark.parametrize("n,d", [(256, 96), (96, 256), (160, 160)])
def test_smoothness_bound_equals_jax_norm_on_cpu(n, d):
    """The host-LAPACK bound is the float the JAX formula gives on the
    CPU: step sizes, and with them every committed round count, depend
    on its last bit."""
    prob = make_random_erm(n=n, d=d, loss="logistic", lam=1e-2, seed=1)
    smax = jnp.linalg.norm(prob.A, ord=2)
    want = float(prob.loss.smoothness * smax ** 2 / prob.n + prob.lam)
    assert prob.smoothness_bound() == want
