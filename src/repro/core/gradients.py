"""Assembly of the stochastic marginal-likelihood gradient (paper eq. 5).

Given the solved batch V = [v_y, v_1..v_s] of H [v_y, v_*] = [y, b_*], the
gradient estimate for every hyperparameter is a sum of quadratic forms

    grad_k = 1/2 v_y^T (dH/dtheta_k) v_y  -  1/(2s) sum_j u_j^T (dH/dtheta_k) w_j

with (u_j, w_j) = (v_j, z_j) for the standard estimator (eq. 6) and
(v_j, v_j) for the pathwise estimator (eq. 9).

TPU/JAX adaptation: instead of materialising the d+2 matrices dH/dtheta_k
and running one MVM each (the GPyTorch/CUDA pattern), we differentiate the
*scalar*

    S(theta) = sum_t w_t a_t^T H(theta) b_t

with the solution vectors stop-gradiented. Its kernel part and that part's
gradient wrt (lengthscales, signal) come from ONE sweep over (bm x bn)
kernel tiles (``_kernel_quadratic``, a ``jax.custom_vjp``): each tile is
recomputed from its row and column blocks of ``u = x / ell`` and reduced at
once, so live memory is O(bm * bn) and nothing of size n^2 is stored for
reverse mode. Per tile, with ``C = (a_r * w) b_c^T`` and
``G = kappa'(r2) * C``, the sweep accumulates ``sum kappa(r2) * C`` and, per
input dimension k, ``sum_ij G_ij (u_ik - u_jk)^2`` in its expanded form (the
algebra reverse-mode AD through the tiled MVM performs). Plain JAX AD keeps
the softplus of the raw hyperparameters and the noise term.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.estimators import PATHWISE, STANDARD
from repro.gp.hyperparams import HyperParams, resolve_kind
from repro.gp.kernels_math import scaled_sqdist
from repro.kernels.registry import _R2_FLOOR, MVM_PRECISION, get_kernel


class GradAux(NamedTuple):
    """Diagnostics returned alongside the MLL gradient estimate."""

    data_fit: jax.Array  # -1/2 y^T v_y (the quadratic MLL term, for logging)
    quad_value: jax.Array  # value of the surrogate S (diagnostic)


def _tile_sweep(lengthscales, x, aw, b, kind, bm, bn):
    """(sum_ij kappa_ij C_ij, T) over all tiles, C = aw b^T, never stored.

    T_k = sum_ij kappa'_ij C_ij (u_ik - u_jk)^2 with u = x / lengthscales,
    in the expanded form ``u_i^2 rowsum + u_j^2 colsum - 2 u_i (G u_c)_i``.
    Zero-padded rows of ``aw`` and ``b`` make C, and so the padded tiles'
    contributions, exactly zero.
    """
    n, d = x.shape
    t = b.shape[1]
    bm = min(bm, n)
    bn = min(bn, n)
    nb_m = -(-n // bm)
    nb_n = -(-n // bn)
    xr = jnp.pad(x, ((0, nb_m * bm - n), (0, 0))).reshape(nb_m, bm, d)
    ar = jnp.pad(aw, ((0, nb_m * bm - n), (0, 0))).reshape(nb_m, bm, t)
    xc = jnp.pad(x, ((0, nb_n * bn - n), (0, 0))).reshape(nb_n, bn, d)
    bc = jnp.pad(b, ((0, nb_n * bn - n), (0, 0))).reshape(nb_n, bn, t)
    spec = get_kernel(kind)

    def row_tile(rows):
        x_r, a_r = rows
        u_r = x_r / lengthscales

        def col_step(acc, cols):
            x_c, b_c = cols
            u_c = x_c / lengthscales
            r2 = scaled_sqdist(x_r, x_c, lengthscales)
            # kappa' as reverse mode sees it through the clamps: zero where
            # the distance was clamped to 0 or below the profile's floor.
            dk = jnp.where(r2 > _R2_FLOOR, spec.dkappa_dr2(r2), 0.0)
            c = jnp.matmul(a_r, b_c.T, precision=MVM_PRECISION)
            g = dk * c
            gu = jnp.matmul(g, u_c, precision=MVM_PRECISION)  # (bm, d)
            t_tile = (
                jnp.sum(u_r * u_r * jnp.sum(g, axis=1)[:, None], axis=0)
                + jnp.sum(u_c * u_c * jnp.sum(g, axis=0)[:, None], axis=0)
                - 2.0 * jnp.sum(u_r * gu, axis=0)
            )
            s_tile = jnp.sum(spec.kappa_from_r2(r2) * c)
            return (acc[0] + s_tile, acc[1] + t_tile), None

        acc0 = (jnp.zeros((), x.dtype), jnp.zeros((d,), x.dtype))
        acc, _ = jax.lax.scan(col_step, acc0, (xc, bc))
        return acc

    s_rows, t_rows = jax.lax.map(row_tile, (xr, ar))
    return jnp.sum(s_rows), jnp.sum(t_rows, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kernel_quadratic(lengthscales, signal, x, aw, b, kind, bm, bn):
    """sum_t aw_t^T K(x, x; lengthscales, signal) b_t."""
    return _kernel_quadratic_fwd(lengthscales, signal, x, aw, b, kind,
                                 bm, bn)[0]


def _kernel_quadratic_fwd(lengthscales, signal, x, aw, b, kind, bm, bn):
    s_unit, t = _tile_sweep(lengthscales, x, aw, b, kind, bm, bn)
    sig2 = signal**2
    d_lengthscales = -2.0 * sig2 * t / lengthscales
    d_signal = 2.0 * signal * s_unit
    return sig2 * s_unit, (d_lengthscales, d_signal)


def _kernel_quadratic_bwd(kind, bm, bn, res, g):
    d_lengthscales, d_signal = res
    return g * d_lengthscales, g * d_signal, None, None, None


_kernel_quadratic.defvjp(_kernel_quadratic_fwd, _kernel_quadratic_bwd)


def _weighted_quadratic(
    params: HyperParams,
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    weights: jax.Array,
    kind: str,
    bm: int,
    bn: int,
) -> jax.Array:
    """S(theta) = sum_t weights_t * a[:, t]^T H(theta) b[:, t]."""
    aw = a * weights
    kab = _kernel_quadratic(params.lengthscales, params.signal, x, aw, b,
                            resolve_kind(kind, params), bm, bn)
    return kab + (params.noise**2) * jnp.sum(aw * b)


def mll_grad_estimate(
    x: jax.Array,
    y: jax.Array,
    params: HyperParams,
    v: jax.Array,
    targets: jax.Array,
    estimator: str,
    kind: Optional[str] = None,
    bm: int = 1024,
    bn: int = 1024,
):
    """Stochastic gradient of L wrt the raw hyperparameters.

    Args:
      v: (n, 1+s) solver solutions [v_y | v_1..v_s].
      targets: (n, 1+s) right-hand sides [y | b_1..b_s].
    Returns:
      (grads: HyperParams-pytree, GradAux)
    """
    s = v.shape[1] - 1
    v = jax.lax.stop_gradient(v)
    targets = jax.lax.stop_gradient(targets)
    v_y = v[:, :1]
    if estimator == STANDARD:
        a = jnp.concatenate([v_y, v[:, 1:]], axis=1)
        b = jnp.concatenate([v_y, targets[:, 1:]], axis=1)
    elif estimator == PATHWISE:
        a = jnp.concatenate([v_y, v[:, 1:]], axis=1)
        b = a
    else:
        raise ValueError(f"unknown estimator {estimator!r}")
    weights = jnp.concatenate(
        [jnp.array([0.5], dtype=v.dtype), jnp.full((s,), -0.5 / s, dtype=v.dtype)]
    )

    quad, grads = jax.value_and_grad(_weighted_quadratic)(
        params, x, a, b, weights, kind, bm, bn
    )
    data_fit = -0.5 * jnp.sum(y * v[:, 0])
    return grads, GradAux(data_fit=data_fit, quad_value=quad)


def exact_grad_reference(
    x: jax.Array,
    y: jax.Array,
    params: HyperParams,
    kind: Optional[str] = None,
):
    """Dense-Cholesky exact gradient (paper's reference; tests only)."""
    from repro.gp.exact import exact_mll

    return jax.grad(lambda p: exact_mll(x, y, p, kind=kind))(params)
