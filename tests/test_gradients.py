"""The gradient's tile sweep against reverse-mode AD through the tiled MVM.

``mll_grad_estimate`` recomputes each kernel tile in one sweep and applies
the gradient by a custom VJP; the reference here is the formulation it
replaced: ``jax.value_and_grad`` of ``sum_t w_t a_t^T H b_t`` with ``K b``
from ``kernel_mvm_tiled``, which stores every tile for reverse mode.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import PATHWISE, STANDARD, mll_grad_estimate
from repro.gp.hyperparams import HyperParams
from repro.solvers.operator import kernel_mvm_tiled

TILE = 64
RTOL = 1e-5


def _reference(x, params, v, targets, estimator, kind):
    s = v.shape[1] - 1
    b = v if estimator == PATHWISE else jnp.concatenate(
        [v[:, :1], targets[:, 1:]], axis=1)
    weights = jnp.concatenate([jnp.array([0.5]), jnp.full((s,), -0.5 / s)])

    def quad(p):
        kb = kernel_mvm_tiled(x, x, b, p, kind=kind, bm=TILE, bn=TILE)
        terms = weights * jnp.sum(v * (kb + p.noise**2 * b), axis=0)
        return jnp.sum(terms), jnp.sum(jnp.abs(terms))

    # ((S, scale), grads): S is a signed sum of s + 1 terms, so its rounding
    # is relative to the terms' magnitude, not to S itself.
    return jax.value_and_grad(quad, has_aux=True)(params)


def _problem(n, d, kind, seed=0, s=8):
    kx, kv, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.uniform(kx, (n, d), minval=-2.0, maxval=2.0)
    x = x.at[5].set(x[7]).at[n - 1].set(x[0])  # duplicated rows: r2 = 0
    v = jax.random.normal(kv, (n, 1 + s))
    targets = jax.random.normal(kt, (n, 1 + s))
    params = HyperParams.create(d, lengthscale=0.8, signal=1.3, noise=0.4,
                                kernel=kind)
    params = params._replace(
        raw_lengthscales=params.raw_lengthscales
        + 0.3 * jnp.sin(jnp.arange(d, dtype=jnp.float32)))
    return x, v, targets, params


def _flat(grads):
    return jnp.concatenate([g.reshape(-1) for g in jax.tree.leaves(grads)])


def _assert_close(grads, quad, ref, ref_grads):
    ref_quad, scale = ref
    got, want = _flat(grads), _flat(ref_grads)
    assert bool(jnp.all(jnp.isfinite(got)))
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < RTOL, err
    assert abs(float(quad - ref_quad)) < RTOL * float(scale)


@pytest.mark.parametrize("kind, estimator, n, d", [
    *[(k, e, 200, 3) for k in ("rbf", "matern12", "matern32", "matern52")
      for e in (PATHWISE, STANDARD)],
    ("matern32", PATHWISE, 96, 1),
    ("matern32", STANDARD, 150, 26),
])
def test_sweep_gradient_matches_reverse_mode_through_tiled_mvm(
        kind, estimator, n, d):
    x, v, targets, params = _problem(n, d, kind)
    grads, aux = mll_grad_estimate(x, targets[:, 0], params, v, targets,
                                   estimator, bm=TILE, bn=TILE)
    ref, ref_grads = _reference(x, params, v, targets, estimator, kind)
    _assert_close(grads, aux.quad_value, ref, ref_grads)


def test_sweep_gradient_under_vmap_over_lanes():
    """Two lanes with their own hyperparameters and solutions, as
    ``outer_step_lanes`` batches them."""
    x, v, targets, params = _problem(130, 3, "matern32")
    lanes = jax.tree.map(lambda a: jnp.stack([a, a - 0.4]), params)
    vs = jnp.stack([v, jnp.roll(v, 3, axis=0)])

    def one(p, vi):
        return mll_grad_estimate(x, targets[:, 0], p, vi, targets, PATHWISE,
                                 bm=TILE, bn=TILE)

    grads, aux = jax.jit(jax.vmap(one))(lanes, vs)
    for lane in range(2):
        p = jax.tree.map(lambda a, i=lane: a[i], lanes)
        ref, ref_grads = _reference(x, p, vs[lane], targets, PATHWISE,
                                    "matern32")
        _assert_close(jax.tree.map(lambda a, i=lane: a[i], grads),
                      aux.quad_value[lane], ref, ref_grads)
