"""Seeded synthetic GP-regression data at a published (n, d) shape.

The benchmark's own copy of the generator behind the program's synthetic
UCI stand-ins, so that a change to the program's random-feature code cannot
change the benchmark's data. Inputs are uniform on a box; targets are one
Matérn-3/2 prior function drawn with random Fourier features plus Gaussian
noise; inputs and targets are then standardised over the rows drawn (all of
them are the training split).

A configuration's dataset is one fixed draw (its ``data.seed``), as a UCI
dataset is one fixed table; a run's ``--seed`` puts its rows in an order of
its own. How long a fit to tolerance takes depends on the data (60 to 110
CG iterations per step on ``pol``), so every seed gets the same data, in
another order. The draw and the shuffle are one jitted call on the default
device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def make_data(key: jax.Array, n: int, d: int, *, lengthscale: float,
              noise: float, num_features: int, box: float):
    """(x, y) of shapes (n, d) and (n,), float32, standardised.

    ``lengthscale`` is the generating Matérn-3/2 lengthscale; ``box`` the
    half-width of the input box; ``num_features`` the sin/cos pairs of the
    prior sample.
    """
    kx, kz, ku, kw, ke = jax.random.split(key, 5)
    x = jax.random.uniform(kx, (n, d), jnp.float32, -box, box)
    # Matérn-3/2 spectral density: a Student-t with 3 degrees of freedom,
    # omega = z * sqrt(3 / u) / lengthscale with u ~ chi^2_3 = 2 Gamma(3/2).
    z = jax.random.normal(kz, (num_features, d), jnp.float32)
    u = 2.0 * jax.random.gamma(ku, 1.5, (num_features,), jnp.float32)
    omega = z * jnp.sqrt(3.0 / u)[:, None] / lengthscale
    proj = jnp.matmul(x, omega.T, precision=HIGHEST)
    phi = jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=1)
    w = jax.random.normal(kw, (2 * num_features,), jnp.float32)
    f = jnp.matmul(phi, w, precision=HIGHEST) / jnp.sqrt(num_features)
    y = f + noise * jax.random.normal(ke, (n,), jnp.float32)
    x = (x - x.mean(axis=0)) / (x.std(axis=0) + 1e-8)
    y = (y - y.mean()) / (y.std() + 1e-8)
    return x, y


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a whole number of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@partial(jax.jit, static_argnames=("n", "d", "num_features", "box"))
def _shuffled(data_key, order_key, n, d, lengthscale, noise, num_features,
              box):
    x, y = make_data(data_key, n, d, lengthscale=lengthscale, noise=noise,
                     num_features=num_features, box=box)
    perm = jax.random.permutation(order_key, n)
    return x[perm], y[perm]


def data_for(config: dict, seed: int):
    """The configuration's training data, rows in ``seed``'s order."""
    gen = config["data"]
    d = int(config["d"])
    return _shuffled(seed_key(gen["seed"]), seed_key(seed),
                     int(config["n_train"]), d,
                     gen["lengthscale_per_sqrt_d"] * d ** 0.5, gen["noise"],
                     gen["num_features"], gen["box"])
