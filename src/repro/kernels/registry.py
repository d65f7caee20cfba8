"""Registry of stationary kernel profiles shared by every MVM backend.

The paper's solver machinery (pathwise estimator, warm starting, epoch
budgets) is kernel-agnostic: every backend only ever needs

  * the *unit* scalar profile ``kappa(r^2)`` of the lengthscale-scaled
    squared distance (signal**2 and the noise diagonal are applied by the
    callers, where plain JAX AD picks up their gradients),
  * its derivative ``dkappa/dr^2`` — the single quantity the fused Pallas
    backward distance-tile kernel applies in VREGs (repro.kernels.tiled),
  * a spectral mixture sampler for RFF prior draws (repro.gp.rff):
    Matérn-nu spectral densities are multivariate Student-t with 2*nu
    degrees of freedom, i.e. Gaussian scale mixtures ``omega = z *
    sqrt(2 nu / u)`` with ``u ~ chi^2_{2 nu}``; the RBF density is plain
    Gaussian (``u`` degenerate at 1).

Each :class:`KernelSpec` bundles exactly those three ingredients, so
registering one spec makes a kernel available to the dense reference
(`repro.gp.kernels_math`), the streamed/tiled jnp backends
(`repro.solvers.operator`), the fused Pallas path (`repro.kernels`), the
distributed ring MVM and the RFF sampler simultaneously.

Everything takes the SQUARED scaled distance so profiles that do not need
``r`` (RBF) never pay a sqrt, and profiles that do share one floor constant
that keeps the sqrt differentiable at coincident points.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

# Precision of every contraction in a kernel MVM (distance cross term and
# the kernel-tile product). On a TPU the default f32 matmul is one bf16
# pass, which put ~3e-3 normwise relative error on K @ v at pol's shape on a
# v5e (fp32 products: ~1e-6); the cross term also cancels in
# ``uu + ww - 2 cross``. HIGHEST keeps the fp32 semantics the solvers and
# the dense references assume; bf16 tiles are a separate, opt-in choice.
MVM_PRECISION = jax.lax.Precision.HIGHEST

SQRT3 = 1.7320508075688772
SQRT5 = 2.23606797749979

# Keeps sqrt(r2) differentiable at coincident points. The floor MUST be
# applied as ``maximum(r2, floor)`` — not ``r2 + floor`` — so reverse-mode AD
# sees an exactly-zero derivative below the floor: with ``+`` the chain rule
# forms dkappa/dr * 1/(2*sqrt(floor)) ~ 0 * 5e14 on the clamped diagonal,
# which only cancels under favourable XLA fusion orders and otherwise
# poisons lengthscale gradients. Matérn-1/2 uses a larger floor: its
# dkappa/dr^2 ~ -1/(2r) diverges as r -> 0 and amplifies diagonal round-off
# in the fused backward tile accumulation; its registered dkappa is
# additionally zeroed on the clamped region (see _m12_dkappa) so coincident
# points contribute exactly nothing instead of the floored slope.
_R2_FLOOR = 1e-30
_R2_FLOOR_M12 = 1e-12


class KernelSpec(NamedTuple):
    """One stationary kernel's contribution to every compute backend.

    Attributes:
      name: registry key (e.g. ``"matern32"``).
      nu: Matérn smoothness, or None for RBF (infinitely smooth limit).
      kappa_from_r2: unit profile ``kappa(r2)`` with ``kappa(0) = 1``;
        evaluated per-tile in VREGs by the Pallas forward kernel and densely
        by the jnp reference/streamed backends.
      dkappa_dr2: ``d kappa / d r2`` — contracted against the outer-product
        cotangent in the fused Pallas backward tile kernel.
      mixture_sample: ``(key, num_pairs, dtype) -> u`` base mixture draws,
        shape (num_pairs,); drawn ONCE under the warm-start contract.
      mixture_scale: ``u -> per-frequency scale`` multiplying the standard
        normal directions ``z`` (deterministic in ``u``).
    """

    name: str
    nu: Optional[float]
    kappa_from_r2: Callable[[jax.Array], jax.Array]
    dkappa_dr2: Callable[[jax.Array], jax.Array]
    mixture_sample: Callable[..., jax.Array]
    mixture_scale: Callable[[jax.Array], jax.Array]


KERNELS: dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    """Register (or override) a kernel for all backends; returns the spec."""
    KERNELS[spec.name] = spec
    return spec


def get_kernel(name: str) -> KernelSpec:
    try:
        return KERNELS[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel {name!r}; registered: {sorted(KERNELS)}"
        ) from None


def available_kernels() -> tuple[str, ...]:
    return tuple(sorted(KERNELS))


# -- profiles ---------------------------------------------------------------


def _rbf_kappa(r2):
    return jnp.exp(-0.5 * r2)


def _rbf_dkappa(r2):
    return -0.5 * jnp.exp(-0.5 * r2)


def _m12_kappa(r2):
    r = jnp.sqrt(jnp.maximum(r2, _R2_FLOOR_M12))
    return jnp.exp(-r)


def _m12_dkappa(r2):
    """Subgradient-aware Matérn-1/2 derivative.

    exp(-r) is non-smooth at r=0 and dkappa/dr2 = -exp(-r)/(2r) diverges
    there. On the clamped region (r2 <= floor — exact duplicates and the
    tile diagonal, where the distance computation lands at hard zero) the
    true contribution to any hyperparameter gradient is zero: dr2/dtheta
    vanishes quadratically while the profile subdifferential stays bounded.
    Returning the FLOORED slope -1/(2*sqrt(floor)) ~ -5e5 instead (as the
    pre-fix code did) plants huge entries in the fused backward tile's
    D = (g v^T) . dkappa, whose row-sum/GEMM cancellation then amplifies
    fp32 round-off into a visible lengthscale-gradient bias on clustered or
    duplicated inputs. So: exact zero below the floor — matching what plain
    AD of ``kappa_from_r2`` produces through the ``maximum`` clamp — and
    the true slope above it.
    """
    r = jnp.sqrt(jnp.maximum(r2, _R2_FLOOR_M12))
    slope = -jnp.exp(-r) / (2.0 * r)
    return jnp.where(r2 > _R2_FLOOR_M12, slope, jnp.zeros_like(slope))


def _m32_kappa(r2):
    r = jnp.sqrt(jnp.maximum(r2, _R2_FLOOR))
    return (1.0 + SQRT3 * r) * jnp.exp(-SQRT3 * r)


def _m32_dkappa(r2):
    r = jnp.sqrt(jnp.maximum(r2, _R2_FLOOR))
    return -1.5 * jnp.exp(-SQRT3 * r)


def _m52_kappa(r2):
    r = jnp.sqrt(jnp.maximum(r2, _R2_FLOOR))
    return (1.0 + SQRT5 * r + (5.0 / 3.0) * r2) * jnp.exp(-SQRT5 * r)


def _m52_dkappa(r2):
    r = jnp.sqrt(jnp.maximum(r2, _R2_FLOOR))
    return -(5.0 / 6.0) * (1.0 + SQRT5 * r) * jnp.exp(-SQRT5 * r)


# -- spectral mixtures ------------------------------------------------------


def _ones_sample(key, num_pairs, dtype=jnp.float32):
    return jnp.ones((num_pairs,), dtype=dtype)


def _chi2_sample(dof: float):
    # chi^2_k = 2 * Gamma(shape=k/2, scale=1)
    def sample(key, num_pairs, dtype=jnp.float32):
        return 2.0 * jax.random.gamma(key, dof / 2.0, (num_pairs,), dtype=dtype)

    return sample


def _chi2_1_sample_stratified(key, num_pairs, dtype=jnp.float32):
    """Stratified (randomised-QMC) chi^2_1 mixture draws for Matérn-1/2.

    The Matérn-1/2 spectral density is Cauchy: the mixture scale
    ``sqrt(1/u)`` has no mean, so iid ``u ~ chi^2_1`` draws under- or
    over-represent the frequency tail at any practical feature count and
    the RFF covariance estimate converges slowly. One jittered
    inverse-CDF draw per probability stratum fixes the tail coverage by
    construction — exactly one frequency per quantile bin, every seed —
    while staying unbiased (the jitter is uniform within each stratum).
    chi^2_1 inverts through the normal CDF: ``u = Phi^{-1}((1+p)/2)^2``.
    Deterministic given ``key``, so the warm-start fixed-base-draw
    contract (gp.rff) is untouched.
    """
    jitter = jax.random.uniform(key, (num_pairs,), dtype=dtype)
    p = (jnp.arange(num_pairs, dtype=dtype) + jitter) / num_pairs
    # Keep ndtri's argument strictly inside (0.5, 1): in float32 the top
    # stratum's (1+p)/2 can round to exactly 1.0 (ndtri -> inf, poisoning
    # the stored u and every downstream feature map).
    q = jnp.minimum((1.0 + p) / 2.0, 1.0 - jnp.finfo(dtype).epsneg)
    z = jax.scipy.special.ndtri(q).astype(dtype)
    # First stratum can land at p ~ 0 -> u ~ 0 -> an infinite mixture
    # scale; clamp to the smallest positive normal (still a ~1e19x scale).
    return jnp.maximum(z * z, jnp.finfo(dtype).tiny)


def _student_scale(dof: float):
    def scale(u):
        return jnp.sqrt(dof / u)

    return scale


register_kernel(KernelSpec(
    name="rbf",
    nu=None,
    kappa_from_r2=_rbf_kappa,
    dkappa_dr2=_rbf_dkappa,
    mixture_sample=_ones_sample,
    mixture_scale=lambda u: jnp.ones_like(u),
))

register_kernel(KernelSpec(
    name="matern12",
    nu=0.5,
    kappa_from_r2=_m12_kappa,
    dkappa_dr2=_m12_dkappa,
    # Stratified, not iid: the Cauchy spectrum's tail is too heavy for
    # plain chi^2_1 draws at practical feature counts (see gp.rff, which
    # also gives matern12 a larger default feature count).
    mixture_sample=_chi2_1_sample_stratified,
    mixture_scale=_student_scale(1.0),
))

register_kernel(KernelSpec(
    name="matern32",
    nu=1.5,
    kappa_from_r2=_m32_kappa,
    dkappa_dr2=_m32_dkappa,
    mixture_sample=_chi2_sample(3.0),
    mixture_scale=_student_scale(3.0),
))

register_kernel(KernelSpec(
    name="matern52",
    nu=2.5,
    kappa_from_r2=_m52_kappa,
    dkappa_dr2=_m52_dkappa,
    mixture_sample=_chi2_sample(5.0),
    mixture_scale=_student_scale(5.0),
))
