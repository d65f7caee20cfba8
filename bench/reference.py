"""Plain reference for the fit path: kernel MVMs, residuals and gradients.

Straightforward ``jax.numpy`` in float32, every contraction at
``Precision.HIGHEST``, distances from coordinate differences (no
``|a|^2 + |b|^2 - 2ab`` expansion), computed in row blocks so that the
n x n kernel matrix is never held whole. It imports nothing of the program.

For H(theta) = s^2 kappa(r^2) + sigma^2 I with r^2 = sum_i (dx_i / l_i)^2
and solutions V = [v_y | v_1..v_s], the pathwise marginal-likelihood
gradient estimate is

    g_k = 1/2 v_y^T dH/dtheta_k v_y - 1/(2s) sum_j v_j^T dH/dtheta_k v_j,

taken with respect to the raw (softplus-inverse) hyperparameters. The
probe systems' right-hand sides are prior samples plus noise,

    b_j = s sqrt(1/m) [cos(x W^T), sin(x W^T)] w_j + sigma eps_j,
    W = Z * sqrt(dof / u) / l,

from base draws (Z, u, w, eps) that are data fed to both sides: the
reference rebuilds b_j from them at the hyperparameters of the solve.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# Degrees of freedom of each Matérn kernel's Student-t spectral density.
SPECTRAL_DOF = {"matern12": 1.0, "matern32": 3.0, "matern52": 5.0}
SQRT3 = 3.0 ** 0.5
SQRT5 = 5.0 ** 0.5


def _r(r2):
    return jnp.sqrt(jnp.maximum(r2, 0.0))


def kappa(kind: str, r2: jax.Array) -> jax.Array:
    """The unit kernel profile kappa(r^2), kappa(0) = 1."""
    if kind == "rbf":
        return jnp.exp(-0.5 * r2)
    r = _r(r2)
    if kind == "matern12":
        return jnp.exp(-r)
    if kind == "matern32":
        return (1.0 + SQRT3 * r) * jnp.exp(-SQRT3 * r)
    if kind == "matern52":
        return (1.0 + SQRT5 * r + 5.0 / 3.0 * r2) * jnp.exp(-SQRT5 * r)
    raise ValueError(f"no reference profile for kernel {kind!r}")


def dkappa_dr2(kind: str, r2: jax.Array) -> jax.Array:
    """d kappa / d r^2. Matérn-1/2's slope diverges at r = 0, where every
    lengthscale derivative carries a factor dx_i^2 = 0: it is set to 0."""
    if kind == "rbf":
        return -0.5 * jnp.exp(-0.5 * r2)
    r = _r(r2)
    if kind == "matern12":
        safe = jnp.where(r > 0, r, 1.0)
        return jnp.where(r > 0, -jnp.exp(-safe) / (2.0 * safe), 0.0)
    if kind == "matern32":
        return -1.5 * jnp.exp(-SQRT3 * r)
    if kind == "matern52":
        return -5.0 / 6.0 * (1.0 + SQRT5 * r) * jnp.exp(-SQRT5 * r)
    raise ValueError(f"no reference profile for kernel {kind!r}")


def split_theta(theta: jax.Array, d: int):
    """(lengthscales (d,), signal, noise) from a flat constrained vector."""
    return theta[:d], theta[d], theta[d + 1]


def _row_blocks(a: jax.Array, block: int):
    n = a.shape[0]
    nb = -(-n // block)
    pad = [(0, nb * block - n)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad).reshape((nb, block) + a.shape[1:])


@partial(jax.jit, static_argnames=("kind", "block"))
def h_mvm(x: jax.Array, theta: jax.Array, v: jax.Array, kind: str,
          block: int = 128) -> jax.Array:
    """H(theta) @ v for v of shape (n, t)."""
    n, d = x.shape
    ell, signal, noise = split_theta(theta, d)
    u = x / ell

    def one(ub):
        r2 = jnp.sum((ub[:, None, :] - u[None, :, :]) ** 2, axis=-1)
        return jnp.matmul(kappa(kind, r2), v, precision=HIGHEST)

    kv = jax.lax.map(one, _row_blocks(u, block))
    kv = kv.reshape(-1, v.shape[1])[:n]
    return signal ** 2 * kv + noise ** 2 * v


def relative_residual(x, theta, v, b, kind: str, block: int = 128):
    """||b - H v|| / ||b|| per column, for v and b of shape (n, t)."""
    r = b - h_mvm(x, theta, v, kind, block)
    return jnp.linalg.norm(r, axis=0) / jnp.linalg.norm(b, axis=0)


@partial(jax.jit, static_argnames=("kind", "block"))
def raw_gradient(x: jax.Array, theta: jax.Array, v: jax.Array, kind: str,
                 block: int = 64) -> jax.Array:
    """The pathwise gradient estimate, (d + 2,): lengthscales, signal,
    noise, with respect to the raw hyperparameters nu (theta =
    softplus(nu), so d theta / d nu = 1 - exp(-theta))."""
    n, d = x.shape
    s = v.shape[1] - 1
    ell, signal, noise = split_theta(theta, d)
    w = jnp.concatenate([jnp.array([0.5], jnp.float32),
                         jnp.full((s,), -0.5 / s, jnp.float32)])
    vw = v * w

    def one(args):
        xb, vwb = args
        diff2 = (xb[:, None, :] - x[None, :, :]) ** 2  # (block, n, d)
        r2 = jnp.sum(diff2 / ell ** 2, axis=-1)
        c = jnp.matmul(vwb, v.T, precision=HIGHEST)  # sum_t w_t v_pt v_qt
        q_signal = jnp.sum(kappa(kind, r2) * c)
        q_ell = jnp.einsum("pq,pqi->i", dkappa_dr2(kind, r2) * c, diff2,
                           precision=HIGHEST)
        return q_signal, q_ell

    # Padded rows carry v = 0, so they add nothing.
    q_signal, q_ell = jax.lax.map(
        one, (_row_blocks(x, block), _row_blocks(vw, block)))
    g_ell = signal ** 2 * jnp.sum(q_ell, axis=0) * (-2.0 / ell ** 3)
    g_signal = 2.0 * signal * jnp.sum(q_signal)
    g_noise = 2.0 * noise * jnp.sum(vw * v)
    g_theta = jnp.concatenate([g_ell, g_signal[None], g_noise[None]])
    return g_theta * (1.0 - jnp.exp(-theta))


def _operand(a: jax.Array, bf16: bool) -> jax.Array:
    """A matmul operand as one bf16 pass reads it, or as it is."""
    return a.astype(jnp.bfloat16).astype(jnp.float32) if bf16 else a


@partial(jax.jit, static_argnames=("kind", "bf16_features"))
def probe_targets(x, theta, z, u, w, eps, kind: str,
                  bf16_features: bool) -> jax.Array:
    """The probe right-hand sides b (n, s) at ``theta``.

    ``bf16_features``: the configuration computes the prior samples' two
    contractions in one bf16 pass (the platform's default matmul precision
    on a TPU); their operands are then rounded to bf16 here too, and the
    products summed in float32.
    """
    d = x.shape[1]
    ell, signal, noise = split_theta(theta, d)
    scale = (jnp.sqrt(SPECTRAL_DOF[kind] / u) if kind in SPECTRAL_DOF
             else jnp.ones_like(u))
    omega = z * scale[:, None] / ell
    proj = jnp.matmul(_operand(x, bf16_features),
                      _operand(omega, bf16_features).T, precision=HIGHEST)
    amp = signal * jnp.sqrt(1.0 / z.shape[0])
    phi = amp * jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=-1)
    f = jnp.matmul(_operand(phi, bf16_features), _operand(w, bf16_features),
                   precision=HIGHEST)
    return f + noise * eps
