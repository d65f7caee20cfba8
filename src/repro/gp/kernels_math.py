"""Dense/streamed kernel mathematics over the registered stationary kernels.

All kernels are parameterised by per-dimension lengthscales and a scalar
signal scale (paper §2), evaluated as ``k(a, b) = s^2 * kappa(r^2)`` with
``r = ||(a - b) / ell||_2`` the scaled Euclidean distance. The scalar
profiles ``kappa`` live in ``repro.kernels.registry`` (RBF + Matérn family)
and are SHARED with the fused Pallas tile kernels, so dense reference and
tiled hot path agree bit-for-bit on the profile maths.

The *regularised kernel matrix* is ``H_theta = K(x, x) + sigma^2 I``.

These functions are the pure-jnp oracles; the Pallas kernels in
``repro.kernels`` compute tiled/fused versions of the same maths.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.gp.hyperparams import HyperParams, resolve_kind
from repro.kernels.registry import MVM_PRECISION, available_kernels, get_kernel



def scaled_sqdist(x1: jax.Array, x2: jax.Array, lengthscales: jax.Array) -> jax.Array:
    """Pairwise squared distances of lengthscale-scaled inputs.

    Args:
      x1: (n, d); x2: (m, d); lengthscales: (d,).
    Returns:
      (n, m) matrix of ||(x1_i - x2_j)/ell||^2, clamped to >= 0.

    Uses the expanded quadratic form so the cross term is a single GEMM
    (the same contraction the Pallas kernel feeds to the MXU), at
    ``MVM_PRECISION``.
    """
    u = x1 / lengthscales
    v = x2 / lengthscales
    uu = jnp.sum(u * u, axis=-1)  # (n,)
    vv = jnp.sum(v * v, axis=-1)  # (m,)
    cross = jnp.matmul(u, v.T, precision=MVM_PRECISION)  # (n, m)
    r2 = uu[:, None] + vv[None, :] - 2.0 * cross
    return jnp.maximum(r2, 0.0)


def profile_from_r2(kind: str) -> Callable:
    """Signal-scaled profile ``(r2, signal) -> s^2 kappa(r2)`` for ``kind``."""
    spec = get_kernel(kind)

    def profile(r2: jax.Array, signal: jax.Array) -> jax.Array:
        return (signal**2) * spec.kappa_from_r2(r2)

    return profile


# Dense signal-scaled profiles, one per registered kernel. Built at import;
# kernels registered later are reachable via profile_from_r2 / get_kernel.
PROFILES: dict[str, Callable] = {
    name: profile_from_r2(name) for name in available_kernels()
}
_PROFILES = PROFILES  # back-compat alias

# Named profiles of the built-in family (back-compat with the seed API).
rbf_from_r2 = PROFILES["rbf"]
matern12_from_r2 = PROFILES["matern12"]
matern32_from_r2 = PROFILES["matern32"]
matern52_from_r2 = PROFILES["matern52"]


def kernel_matrix(
    x1: jax.Array,
    x2: jax.Array,
    params: HyperParams,
    kind: Optional[str] = None,
) -> jax.Array:
    """Dense cross-kernel matrix K(x1, x2; theta) of shape (n, m)."""
    kind = resolve_kind(kind, params)
    r2 = scaled_sqdist(x1, x2, params.lengthscales)
    return profile_from_r2(kind)(r2, params.signal)


def regularised_kernel_matrix(
    x: jax.Array, params: HyperParams, kind: Optional[str] = None
) -> jax.Array:
    """H_theta = K(x, x) + sigma^2 I (dense; reference/small-n only)."""
    n = x.shape[0]
    k = kernel_matrix(x, x, params, kind=kind)
    return k + (params.noise**2) * jnp.eye(n, dtype=k.dtype)


@partial(jax.jit, static_argnames=("kind", "block_rows"))
def kernel_mvm_streamed(
    x1: jax.Array,
    x2: jax.Array,
    v: jax.Array,
    params: HyperParams,
    kind: Optional[str] = None,
    block_rows: int = 1024,
) -> jax.Array:
    """K(x1, x2) @ v without materialising K — O(block * m) memory.

    Streams over row blocks of x1 with ``lax.map``; each block builds its
    distance tile, applies the profile, and contracts against ``v``.
    This is the pure-jnp analogue of the fused Pallas kernel and the
    single-device form of the distributed ring MVM.

    Args:
      x1: (n, d); x2: (m, d); v: (m, s) or (m,).
    Returns:
      (n, s) or (n,) — K @ v.
    """
    kind = resolve_kind(kind, params)
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    n = x1.shape[0]
    nb = -(-n // block_rows)
    pad = nb * block_rows - n
    x1p = jnp.pad(x1, ((0, pad), (0, 0)))
    blocks = x1p.reshape(nb, block_rows, x1.shape[1])
    profile = profile_from_r2(kind)

    def body(xb):
        r2 = scaled_sqdist(xb, x2, params.lengthscales)
        kb = profile(r2, params.signal)
        return jnp.matmul(kb, v, precision=MVM_PRECISION)

    out = jax.lax.map(body, blocks).reshape(nb * block_rows, v.shape[1])[:n]
    return out[:, 0] if squeeze else out


def h_mvm_dense(
    x: jax.Array, v: jax.Array, params: HyperParams, kind: Optional[str] = None
) -> jax.Array:
    """H_theta @ v via the dense kernel matrix (reference)."""
    h = regularised_kernel_matrix(x, params, kind=kind)
    return h @ v


def h_mvm_streamed(
    x: jax.Array,
    v: jax.Array,
    params: HyperParams,
    kind: Optional[str] = None,
    block_rows: int = 1024,
) -> jax.Array:
    """H_theta @ v = K @ v + sigma^2 v, streamed (no n x n materialisation)."""
    kv = kernel_mvm_streamed(x, x, v, params, kind=kind, block_rows=block_rows)
    return kv + (params.noise**2) * v
