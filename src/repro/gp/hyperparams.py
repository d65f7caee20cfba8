"""GP hyperparameters with softplus reparameterisation (paper Appendix B).

Each positive hyperparameter ``theta_k`` is stored as an unconstrained raw
value ``nu_k`` with ``theta_k = softplus(nu_k) = log(1 + exp(nu_k))`` so the
outer-loop Adam optimiser operates on R^{d_theta} (paper: "to facilitate
unconstrained optimisation").
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


@jax.custom_jvp
def softplus(nu: jax.Array) -> jax.Array:
    """log(1 + exp(nu)) from ``exp`` and arithmetic alone.

    ``max(nu, 0) + log1p(t)`` with ``t = exp(-|nu|)`` in (0, 1], and
    ``log1p(t) = 2 atanh(z)``, ``z = t / (2 + t) <= 1/3``, by its series to
    ``z^15`` (the rest is under 2e-9 of it). The TPU's ``log`` and
    ``log1p``, and so ``jnp.logaddexp``, are off by up to 1e-4 there, which
    moves a reported hyperparameter by a visible part of an Adam step.
    """
    t = jnp.exp(-jnp.abs(nu))
    z = t / (2.0 + t)
    z2 = z * z
    series = 1.0 / 15.0
    for k in (13, 11, 9, 7, 5, 3, 1):
        series = series * z2 + 1.0 / k
    return jnp.maximum(nu, 0.0) + 2.0 * z * series


@softplus.defjvp
def _softplus_jvp(primals, tangents):
    (nu,), (dnu,) = primals, tangents
    sigmoid = jnp.exp(jnp.minimum(nu, 0.0)) / (1.0 + jnp.exp(-jnp.abs(nu)))
    return softplus(nu), sigmoid * dnu


def softplus_inverse(theta: jax.Array) -> jax.Array:
    """Inverse of :func:`softplus`: nu = log(exp(theta) - 1), stable form."""
    # For large theta, expm1(theta) overflows; use theta + log1p(-exp(-theta)).
    theta = jnp.asarray(theta)
    small = theta < 20.0
    safe = jnp.where(small, theta, 1.0)
    return jnp.where(small, jnp.log(jnp.expm1(safe)), theta + jnp.log1p(-jnp.exp(-theta)))


class HyperParams(NamedTuple):
    """Unconstrained GP hyperparameters (a pytree; leaves are raw values).

    Attributes:
      raw_lengthscales: shape (d,), one per input dimension.
      raw_signal: scalar signal scale (sqrt of kernel variance).
      raw_noise: scalar observation noise scale sigma.
      kernel: registered kernel name (repro.kernels.registry) — static pytree
        aux data, not a leaf, so it survives tree maps / Adam / checkpointing
        and acts as the default ``kind`` wherever one is not given explicitly.
    """

    raw_lengthscales: jax.Array
    raw_signal: jax.Array
    raw_noise: jax.Array
    kernel: str = "matern32"

    @property
    def lengthscales(self) -> jax.Array:
        return softplus(self.raw_lengthscales)

    @property
    def signal(self) -> jax.Array:
        return softplus(self.raw_signal)

    @property
    def noise(self) -> jax.Array:
        return softplus(self.raw_noise)

    @property
    def num_params(self) -> int:
        return int(self.raw_lengthscales.shape[0]) + 2

    @staticmethod
    def create(
        d: int,
        lengthscale: float = 1.0,
        signal: float = 1.0,
        noise: float = 1.0,
        dtype=jnp.float32,
        kernel: str = "matern32",
    ) -> "HyperParams":
        """Constrained-space constructor (paper initialises at 1.0)."""
        ls = jnp.full((d,), lengthscale, dtype=dtype)
        return HyperParams(
            raw_lengthscales=softplus_inverse(ls),
            raw_signal=softplus_inverse(jnp.asarray(signal, dtype=dtype)),
            raw_noise=softplus_inverse(jnp.asarray(noise, dtype=dtype)),
            kernel=kernel,
        )

    def constrained(self) -> dict:
        return {
            "lengthscales": self.lengthscales,
            "signal": self.signal,
            "noise": self.noise,
        }

    def flat(self) -> jax.Array:
        """All constrained hyperparameters as one vector (for logging)."""
        return jnp.concatenate(
            [self.lengthscales, self.signal[None], self.noise[None]]
        )


# ``kernel`` rides along as static aux data: tree maps (Adam updates, grads,
# checkpoint restore-by-template) see only the three raw arrays as leaves.
jax.tree_util.register_pytree_node(
    HyperParams,
    lambda p: ((p.raw_lengthscales, p.raw_signal, p.raw_noise), p.kernel),
    lambda kernel, children: HyperParams(*children, kernel=kernel),
)


def resolve_kind(kind, params) -> str:
    """The effective kernel name: an explicit ``kind`` wins over the params'."""
    return kind if kind is not None else params.kernel
