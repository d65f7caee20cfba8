"""The plain reference against float64 NumPy at a small size."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference

N, D, S = 70, 3, 4


def profile(kind, r2):
    r = np.sqrt(r2)
    return {"rbf": np.exp(-0.5 * r2),
            "matern12": np.exp(-r),
            "matern32": (1 + np.sqrt(3) * r) * np.exp(-np.sqrt(3) * r),
            "matern52": (1 + np.sqrt(5) * r + 5 / 3 * r2)
            * np.exp(-np.sqrt(5) * r)}[kind]


def dense_h(x, theta, kind):
    ell, s, sigma = theta[:D], theta[D], theta[D + 1]
    u = x / ell
    r2 = np.sum((u[:, None, :] - u[None, :, :]) ** 2, axis=-1)
    return s ** 2 * profile(kind, r2) + sigma ** 2 * np.eye(len(x))


def surrogate(x, nu, v, kind):
    """sum_t w_t v_t^T H(softplus(nu)) v_t, the pathwise weights."""
    theta = np.log1p(np.exp(nu))
    w = np.array([0.5] + [-0.5 / S] * S)
    h = dense_h(x, theta, kind)
    return float(np.sum(w * np.sum(v * (h @ v), axis=0)))


@pytest.fixture
def problem():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, D))
    v = rng.normal(size=(N, 1 + S))
    theta = np.array([0.7, 1.3, 2.1, 0.9, 0.3])
    return x, v, theta


@pytest.mark.parametrize("kind", ["rbf", "matern12", "matern32", "matern52"])
def test_h_mvm_and_residual(problem, kind):
    x, v, theta = problem
    want = dense_h(x, theta, kind) @ v
    got = reference.h_mvm(jnp.asarray(x, jnp.float32),
                          jnp.asarray(theta, jnp.float32),
                          jnp.asarray(v, jnp.float32), kind, block=16)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    b = want + 0.01 * np.random.default_rng(1).normal(size=v.shape)
    rho = reference.relative_residual(
        jnp.asarray(x, jnp.float32), jnp.asarray(theta, jnp.float32),
        jnp.asarray(v, jnp.float32), jnp.asarray(b, jnp.float32), kind, 16)
    want_rho = np.linalg.norm(b - want, axis=0) / np.linalg.norm(b, axis=0)
    np.testing.assert_allclose(np.asarray(rho), want_rho, rtol=2e-3)


@pytest.mark.parametrize("kind", ["rbf", "matern32", "matern52"])
def test_raw_gradient_is_the_derivative_of_the_surrogate(problem, kind):
    x, v, theta = problem
    nu = np.log(np.expm1(theta))
    h = 1e-6
    fd = np.array([(surrogate(x, nu + h * e, v, kind)
                    - surrogate(x, nu - h * e, v, kind)) / (2 * h)
                   for e in np.eye(D + 2)])
    got = reference.raw_gradient(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(theta, jnp.float32),
                                 jnp.asarray(v, jnp.float32), kind, block=16)
    np.testing.assert_allclose(np.asarray(got), fd, rtol=1e-4,
                               atol=1e-4 * np.max(np.abs(fd)))


def test_raw_from_theta_inverts_softplus():
    from bench.check import _raw

    nu = np.array([-3.0, 0.0, 0.5413, 4.0, 30.0])
    np.testing.assert_allclose(_raw(np.log1p(np.exp(nu))), nu, atol=1e-9)


def test_probe_targets_match_the_program_at_float32():
    """The rebuilt right-hand sides equal the program's on the CPU, where
    its default matmul precision is float32."""
    import jax

    from repro.core.estimators import init_probes, probe_targets
    from repro.gp.hyperparams import HyperParams

    x = jnp.asarray(np.random.default_rng(2).normal(size=(N, D)), jnp.float32)
    params = HyperParams.create(D, lengthscale=0.8, signal=1.3, noise=0.4)
    probes = init_probes(jax.random.PRNGKey(3), "pathwise", N, D, S,
                         num_rff_pairs=32, kind="matern32")
    want = probe_targets(probes, x, params)
    got = reference.probe_targets(x, params.flat(), probes.rff.z,
                                  probes.rff.u, probes.rff.w, probes.w_eps,
                                  "matern32", False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_adam_gap_reads_the_programs_updates_as_sound():
    """Three steps of the program's Adam on known gradients read a gap of
    rounding only; the same steps with the sign of the gradient flipped,
    or frozen after the first, read far more."""
    import jax

    from bench.check import adam_gap, initial_theta
    from repro.gp.hyperparams import HyperParams
    from repro.train.adam import AdamConfig, adam_init, adam_update

    config = {"d": D, "init": {"lengthscale": 1.0, "signal": 1.0,
                                "noise": 1.0}}
    settings = {"lr": 0.1, "betas": (0.9, 0.999), "eps": 1e-8}
    rng = np.random.default_rng(4)
    grads = [rng.normal(size=D + 2) for _ in range(3)]

    def run(sign, freeze):
        params = HyperParams.create(D)
        state = adam_init(params)
        hypers = []
        for t, g in enumerate(grads):
            g_tree = HyperParams(jnp.asarray(sign * g[:D], jnp.float32),
                                 jnp.asarray(sign * g[D], jnp.float32),
                                 jnp.asarray(sign * g[D + 1], jnp.float32))
            new, state = adam_update(g_tree, state, params, AdamConfig(0.1),
                                     maximize=True)
            params = params if freeze and t >= 1 else new
            hypers.append(np.asarray(params.flat()))
        leaves = jax.tree.leaves(state.mu), jax.tree.leaves(state.nu)
        mu, nu = (np.concatenate([np.ravel(x) for x in ls]) for ls in leaves)
        return adam_gap(np.array(hypers), initial_theta(config), mu, nu,
                        grads[-1], settings)

    assert run(1.0, False) < 1e-4
    assert run(-1.0, False) > 0.1
    assert run(1.0, True) > 0.1
