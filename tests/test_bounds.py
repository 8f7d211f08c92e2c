"""Theorem 3.2 / Lemma 3.1 property tests (hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import (
    certify_head,
    rsi_factors,
    softmax_jacobian,
    softmax_perturbation_bound,
)


@settings(max_examples=30, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    C=st.integers(2, 24),
    scale=st.floats(0.1, 20.0),
)
def test_lemma_3_1_jacobian_row_sums(seed, C, scale):
    """Row sums of |J_sigma| equal 2*s_i(1-s_i) and are <= 1/2."""
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (C,))) * scale
    J = np.asarray(softmax_jacobian(jnp.asarray(u)))
    s = np.asarray(jax.nn.softmax(jnp.asarray(u)))
    row_sums = np.abs(J).sum(axis=1)
    np.testing.assert_allclose(row_sums, 2 * s * (1 - s), atol=1e-5)
    assert (row_sums <= 0.5 + 1e-6).all()
    # Jacobian structure: diag(s) - s s^T
    np.testing.assert_allclose(J, np.diag(s) - np.outer(s, s), atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    C=st.integers(3, 16),
    D=st.integers(8, 64),
    k_frac=st.floats(0.2, 0.9),
)
def test_theorem_3_2_bound_holds(seed, C, D, k_frac):
    """||softmax(W~h+b) - softmax(Wh+b)||_inf <= 1/2 R ||W-W~||_2 for random
    W, low-rank W~, and a batch of feature vectors with ||h|| <= R."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    W = jax.random.normal(keys[0], (C, D))
    b = jax.random.normal(keys[1], (C,))
    k = max(1, int(k_frac * min(C, D)))
    A, B = rsi_factors(W, k, 2, keys[2])
    W_approx = A @ B
    h = jax.random.normal(keys[3], (32, D))
    R = float(jnp.max(jnp.linalg.norm(h, axis=-1)))
    spec_err = float(jnp.linalg.svd(W - W_approx, compute_uv=False)[0])

    p = jax.nn.softmax(h @ W.T + b, axis=-1)
    p2 = jax.nn.softmax(h @ W_approx.T + b, axis=-1)
    lhs = float(jnp.max(jnp.abs(p - p2)))
    rhs = float(softmax_perturbation_bound(spec_err, R))
    assert lhs <= rhs + 1e-5, (lhs, rhs)


def test_certificate_end_to_end():
    key = jax.random.PRNGKey(0)
    C, D, k = 10, 64, 4
    W = jax.random.normal(key, (C, D)) * 0.3
    A, B = rsi_factors(W, k, 3, jax.random.PRNGKey(1))
    calib = jax.random.normal(jax.random.PRNGKey(2), (128, D))
    cert = certify_head(W, A @ B, calib, jax.random.PRNGKey(3), rank=k, q=3)
    assert cert.prob_deviation_bound >= 0.5 * cert.spectral_error * 0  # sanity
    # the empirical deviation on calibration data must respect the bound
    p = jax.nn.softmax(calib @ W.T, axis=-1)
    p2 = jax.nn.softmax(calib @ (A @ B).T, axis=-1)
    emp = float(jnp.max(jnp.abs(p - p2)))
    assert emp <= cert.prob_deviation_bound + 1e-4
    # top-1 stability logic
    assert cert.guarantees_top1_stability(margin=2 * cert.prob_deviation_bound + 0.1)
    assert not cert.guarantees_top1_stability(margin=0.0)
