"""Rank-k pivoted Cholesky preconditioner for CG (paper Appendix B, following
Wang et al. [29] / GPyTorch).

Builds a partial pivoted Cholesky factor L (n x k) of the *kernel* matrix K
(without noise) using k greedy pivots, then applies

    P^{-1} r = (L L^T + sigma^2 I)^{-1} r
             = (r - L (sigma^2 I_k + L^T L)^{-1} L^T r) / sigma^2      (Woodbury)

Each pivot step needs exactly one kernel row K[i, :] — O(n * d) work — so the
full preconditioner costs O(k * n * (d + k)) and is negligible next to solver
epochs (k=100).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import scopes
from repro.solvers.operator import HOperator

_JITTER = 1e-10

# Sentinel for SolverConfig.precond_rank: resolve rank/jitter from the
# per-kernel table below instead of a hand-picked number.
AUTO_RANK = -1


class PrecondDefaults(NamedTuple):
    """Per-kernel pivoted-Cholesky settings (see PRECOND_DEFAULTS)."""

    rank: int
    jitter: float


# Per-kernel pivoted-Cholesky defaults. Rank tracks the kernel's eigendecay:
# RBF spectra decay super-exponentially, so a very low-rank factor already
# captures K and larger ranks only buy extra O(n k^2) setup; Matérn spectra
# decay polynomially with smoothness nu, so rougher kernels need more pivots
# to pay off (matern12 also gets a larger inner jitter — its near-diagonal
# Schur complements are noisier under the floored-r profile). Unregistered
# kernels fall back to the paper's rank-100 / Wang et al. setting.
PRECOND_DEFAULTS: dict[str, PrecondDefaults] = {
    "rbf": PrecondDefaults(rank=20, jitter=_JITTER),
    "matern12": PrecondDefaults(rank=150, jitter=1e-8),
    "matern32": PrecondDefaults(rank=100, jitter=_JITTER),
    "matern52": PrecondDefaults(rank=60, jitter=_JITTER),
}

_FALLBACK = PrecondDefaults(rank=100, jitter=_JITTER)


def default_precond(kind: str) -> PrecondDefaults:
    """The rank/jitter defaults for a registered kernel name."""
    return PRECOND_DEFAULTS.get(kind, _FALLBACK)


class Preconditioner(NamedTuple):
    """Partial pivoted-Cholesky preconditioner ``P = LL^T + sigma^2 I``."""

    l: jax.Array  # (n, k) partial pivoted-Cholesky factor of K
    chol_inner: jax.Array  # (k, k) Cholesky of sigma^2 I_k + L^T L
    noise_var: jax.Array  # sigma^2

    def apply(self, r: jax.Array) -> jax.Array:
        """P^{-1} @ r for r of shape (n, t)."""
        with jax.named_scope(scopes.PRECOND):
            ltr = self.l.T @ r  # (k, t)
            inner = jax.scipy.linalg.cho_solve((self.chol_inner, True), ltr)
            return (r - self.l @ inner) / self.noise_var


def identity_preconditioner(n: int, dtype=jnp.float32) -> Preconditioner:
    """Rank-0 stand-in: apply() reduces to the identity (L = 0)."""
    return Preconditioner(
        l=jnp.zeros((n, 1), dtype=dtype),
        chol_inner=jnp.eye(1, dtype=dtype),
        noise_var=jnp.asarray(1.0, dtype=dtype),
    )


def pivoted_cholesky(op: HOperator, rank: int) -> jax.Array:
    """Partial pivoted Cholesky of K (kernel only, no noise): (n, rank).

    Greedy pivot = argmax of the running diagonal of the Schur complement.
    """
    n = op.n
    dtype = op.x.dtype

    def step(carry, j):
        l, d = carry  # l: (n, rank); d: (n,) residual diagonal
        i = jnp.argmax(d)
        row = op.kernel_row(i)  # (n,) K[i, :]
        # Schur correction from previously selected columns.
        li = jax.lax.dynamic_slice(l, (i, 0), (1, rank))[0]  # (rank,)
        row = row - l @ li
        pivot = jnp.sqrt(jnp.maximum(d[i], _JITTER))
        col = row / pivot
        # Exact zero at previously-pivoted rows is implied; numerically we
        # just update the diagonal and clamp.
        l = l.at[:, j].set(col)
        d = jnp.maximum(d - col**2, 0.0)
        d = d.at[i].set(0.0)
        return (l, d), None

    l0 = jnp.zeros((n, rank), dtype=dtype)
    d0 = op.kernel_diag()
    (l, _), _ = jax.lax.scan(step, (l0, d0), jnp.arange(rank))
    return l


def build_preconditioner(op: HOperator, rank: int) -> Preconditioner:
    """Rank-``rank`` preconditioner; 0 disables, AUTO_RANK (< 0) resolves the
    rank and jitter from the per-kernel :data:`PRECOND_DEFAULTS` table."""
    jitter = _JITTER
    if rank < 0:
        defaults = default_precond(op.kernel_kind)
        rank, jitter = defaults.rank, defaults.jitter
    rank = min(rank, op.n)
    if rank <= 0:
        return identity_preconditioner(op.n, dtype=op.x.dtype)
    with jax.named_scope(scopes.PRECOND):
        l = pivoted_cholesky(op, rank)
        inner = op.noise_var * jnp.eye(rank, dtype=l.dtype) + l.T @ l
        inner = inner + jitter * jnp.eye(rank, dtype=l.dtype)
        chol_inner = jnp.linalg.cholesky(inner)
    return Preconditioner(l=l, chol_inner=chol_inner, noise_var=op.noise_var)
