"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Optional dev dependency (listed in the ``dev`` extra): skip this module —
# instead of aborting the whole collection — when it is absent.
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.gp.hyperparams import HyperParams, softplus, softplus_inverse
from repro.gp.kernels_math import (
    kernel_matrix,
    kernel_mvm_streamed,
    regularised_kernel_matrix,
    scaled_sqdist,
)

_settings = settings(max_examples=25, deadline=None)

dims = st.integers(min_value=1, max_value=6)
sizes = st.integers(min_value=2, max_value=40)
scales = st.floats(min_value=0.2, max_value=3.0)


@_settings
@given(sizes, dims, scales, st.integers(0, 2**31 - 1))
def test_kernel_matrix_symmetric_psd(n, d, ls, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    p = HyperParams.create(d, lengthscale=ls, noise=0.3)
    h = np.asarray(regularised_kernel_matrix(x, p))
    np.testing.assert_allclose(h, h.T, rtol=1e-5, atol=1e-5)
    ev = np.linalg.eigvalsh(h)
    assert ev.min() > 0.0  # positive definite (noise regularised)


@_settings
@given(sizes, dims, scales, st.integers(0, 2**31 - 1))
def test_kernel_diag_is_signal_sq_plus_noise(n, d, sig, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    p = HyperParams.create(d, signal=sig, noise=0.5)
    h = np.asarray(regularised_kernel_matrix(x, p))
    np.testing.assert_allclose(
        np.diag(h), sig**2 + 0.25, rtol=1e-4, atol=1e-4
    )


@_settings
@given(sizes, sizes, dims, st.integers(1, 5), st.integers(0, 2**31 - 1))
def test_streamed_mvm_matches_dense(n, m, d, s, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x1 = jax.random.normal(k1, (n, d))
    x2 = jax.random.normal(k2, (m, d))
    v = jax.random.normal(k3, (m, s))
    p = HyperParams.create(d)
    out = kernel_mvm_streamed(x1, x2, v, p, block_rows=7)
    ref = kernel_matrix(x1, x2, p) @ v
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@_settings
@given(st.floats(min_value=1e-3, max_value=50.0))
def test_softplus_roundtrip(theta):
    nu = softplus_inverse(jnp.asarray(theta, jnp.float32))
    back = float(softplus(nu))
    assert abs(back - theta) / theta < 1e-4


def test_softplus_and_its_derivative_to_float32_rounding():
    """No ``log`` inside: the TPU's is off by 1e-4, so the value is held to
    float64 here, everywhere it does not underflow."""
    x = np.linspace(-60.0, 60.0, 240_001, dtype=np.float32)
    exact = np.logaddexp(0.0, np.float64(x))
    sigmoid = 1.0 / (1.0 + np.exp(-np.float64(x)))
    value = np.float64(jax.jit(softplus)(x))
    slope = np.float64(jax.jit(jax.vmap(jax.grad(softplus)))(x))
    for got, want in ((value, exact), (slope, sigmoid)):
        normal = want > np.finfo(np.float32).tiny
        ulps = np.abs(got - want)[normal] / np.spacing(
            np.float32(want[normal]))
        assert ulps.max() < 8.0, ulps.max()


@_settings
@given(sizes, dims, st.integers(0, 2**31 - 1))
def test_scaled_sqdist_nonneg_and_zero_diag(n, d, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    ls = jnp.ones((d,))
    r2 = np.asarray(scaled_sqdist(x, x, ls))
    assert (r2 >= 0).all()
    np.testing.assert_allclose(np.diag(r2), 0.0, atol=1e-4)


@_settings
@given(st.integers(2, 16), st.integers(1, 4), st.integers(0, 2**31 - 1))
def test_solver_invariant_residual_matches_solution(n_blocks, d, seed):
    """For any solved system, the reported relative residual must agree with
    a recomputed residual (no drift in the solver's internal tracking)."""
    from repro.solvers import HOperator, SolverConfig, solve

    n = 16 * n_blocks
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    p = HyperParams.create(d, noise=0.5)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, 3))
    op = HOperator(x=x, params=p, backend="streamed", bm=32, bn=32)
    cfg = SolverConfig(name="cg", tolerance=0.01, max_epochs=500,
                       precond_rank=0)
    res = solve(op, b, None, cfg)
    r = b - op.mvm(res.v)
    rel = np.asarray(jnp.linalg.norm(r, axis=0) /
                     (jnp.linalg.norm(b, axis=0) + 1e-10))
    assert abs(rel[0] - float(res.res_y)) < 5e-3
