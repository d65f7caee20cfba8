"""Matrix-free access to H_theta = K(x, x) + sigma^2 I.

All three solvers (CG / AP / SGD) and both gradient estimators touch H only
through this interface, so backends can be swapped freely:

  * ``dense``    — materialise H once (reference; small n only).
  * ``streamed`` — pure-jnp two-level tiling, O(bm*bn) live memory.
  * ``pallas``   — fused distance-tile TPU kernel for any registered
                   stationary kernel (repro.kernels); validated on CPU via
                   interpret mode.
  * ``ring``     — multi-device shard_map ring MVM (repro.distributed.ring);
                   constructed by the distributed driver.

Block index convention: AP/SGD work on contiguous blocks ``[i*b, (i+1)*b)``;
``n`` must be a multiple of the block size (the data pipeline pads with
far-away pseudo-points whose kernel row is exactly zero, see
``repro.data.synthetic.pad_to_block_multiple``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.gp.hyperparams import HyperParams, resolve_kind
from repro.gp.kernels_math import (
    kernel_matrix,
    profile_from_r2,
    regularised_kernel_matrix,
    scaled_sqdist,
)
from repro.kernels.registry import MVM_PRECISION
from repro.obs import scopes


def kernel_mvm_tiled(
    x1: jax.Array,
    x2: jax.Array,
    v: jax.Array,
    params: HyperParams,
    kind: Optional[str] = None,
    bm: int = 1024,
    bn: int = 1024,
) -> jax.Array:
    """K(x1, x2) @ v with two-level tiling; never materialises K.

    Outer ``lax.map`` over row tiles of x1, inner ``lax.scan`` accumulating
    over column tiles of (x2, v). Live memory is O(bm * bn + bm * s).
    """
    n, d = x1.shape
    m = x2.shape[0]
    s = v.shape[1]
    bm = min(bm, n)
    bn = min(bn, m)
    nb_m = -(-n // bm)
    nb_n = -(-m // bn)
    # Pad rows (extra outputs sliced off) and columns (v padded with zeros so
    # phantom columns contribute nothing).
    x1p = jnp.pad(x1, ((0, nb_m * bm - n), (0, 0)))
    x2p = jnp.pad(x2, ((0, nb_n * bn - m), (0, 0)))
    vp = jnp.pad(v, ((0, nb_n * bn - m), (0, 0)))
    x1b = x1p.reshape(nb_m, bm, d)
    x2b = x2p.reshape(nb_n, bn, d)
    vb = vp.reshape(nb_n, bn, s)
    profile = profile_from_r2(resolve_kind(kind, params))

    def row_tile(xr):
        def col_step(acc, xcvc):
            xc, vc = xcvc
            r2 = scaled_sqdist(xr, xc, params.lengthscales)
            kb = profile(r2, params.signal)
            return acc + jnp.matmul(kb, vc, precision=MVM_PRECISION), None

        acc0 = jnp.zeros((bm, s), dtype=v.dtype)
        acc, _ = jax.lax.scan(col_step, acc0, (x2b, vb))
        return acc

    out = jax.lax.map(row_tile, x1b).reshape(nb_m * bm, s)
    return out[:n]


@dataclass(frozen=True)
class HOperator:
    """H_theta = K(x, x; theta) + sigma^2 I as a linear operator."""

    # repro-lint: disable=config-static-array -- closure-captured operator, frozen for immutability; never hashed into a jit cache key
    x: jax.Array  # (n, d) training inputs
    params: HyperParams
    kind: Optional[str] = None  # None => params.kernel
    backend: str = "streamed"  # dense | streamed | pallas
    bm: int = 1024
    bn: int = 1024
    # Optional externally supplied full-MVM override (e.g. the distributed
    # ring MVM); signature (v: (n, s)) -> (n, s) for K @ v (noise added here).
    kernel_mvm_override: Optional[Callable] = None

    @property
    def n(self) -> int:
        """Number of training rows (the system dimension)."""
        return self.x.shape[0]

    @property
    def kernel_kind(self) -> str:
        """The effective kernel name (explicit kind wins over params.kernel)."""
        return resolve_kind(self.kind, self.params)

    @property
    def noise_var(self) -> jax.Array:
        """The regulariser sigma^2 added to the kernel diagonal."""
        return self.params.noise ** 2

    # -- full MVM ----------------------------------------------------------
    def _kernel_mvm(self, v: jax.Array) -> jax.Array:
        if self.kernel_mvm_override is not None:
            return self.kernel_mvm_override(v)
        if self.backend == "dense":
            k = kernel_matrix(self.x, self.x, self.params, kind=self.kind)
            return k @ v
        if self.backend == "pallas":
            from repro.kernels.ops import kernel_mvm

            return kernel_mvm(
                self.x, self.x, v, self.params, kind=self.kernel_kind,
                bm=self.bm, bn=self.bn,
            )
        return kernel_mvm_tiled(
            self.x, self.x, v, self.params, kind=self.kind, bm=self.bm, bn=self.bn
        )

    def mvm(self, v: jax.Array) -> jax.Array:
        """H @ v for v of shape (n, s) [or (n,)]."""
        squeeze = v.ndim == 1
        if squeeze:
            v = v[:, None]
        with jax.named_scope(scopes.MVM):
            out = self._kernel_mvm(v) + self.noise_var * v
        return out[:, 0] if squeeze else out

    # -- partial access (AP / SGD / pivoted Cholesky) -----------------------
    def x_block(self, start: jax.Array, size: int) -> jax.Array:
        """(size, d) slice of the training inputs starting at row ``start``."""
        return jax.lax.dynamic_slice(self.x, (start, 0), (size, self.x.shape[1]))

    def row_block_mvm(self, start: jax.Array, size: int, v: jax.Array) -> jax.Array:
        """H[blk, :] @ v -> (size, s); one AP/SGD step's worth of kernel evals."""
        xb = self.x_block(start, size)
        kv = kernel_mvm_tiled(
            xb, self.x, v, self.params, kind=self.kind, bm=size, bn=self.bn
        )
        vb = jax.lax.dynamic_slice(v, (start, 0), (size, v.shape[1]))
        return kv + self.noise_var * vb

    def col_block_mvm(self, start: jax.Array, size: int, u: jax.Array) -> jax.Array:
        """H[:, blk] @ u -> (n, s) for u of shape (size, s)."""
        xb = self.x_block(start, size)
        ku = kernel_mvm_tiled(
            self.x, xb, u, self.params, kind=self.kind, bm=self.bm, bn=size
        )
        pad_u = jnp.zeros((self.n, u.shape[1]), dtype=u.dtype)
        pad_u = jax.lax.dynamic_update_slice(pad_u, u, (start, 0))
        return ku + self.noise_var * pad_u

    def block(self, start: jax.Array, size: int) -> jax.Array:
        """H[blk, blk] -> (size, size) dense tile (for AP block Cholesky)."""
        xb = self.x_block(start, size)
        kb = kernel_matrix(xb, xb, self.params, kind=self.kind)
        return kb + self.noise_var * jnp.eye(size, dtype=kb.dtype)

    def kernel_row(self, i: jax.Array) -> jax.Array:
        """K[i, :] (WITHOUT noise) -> (n,); used by pivoted Cholesky."""
        xi = jax.lax.dynamic_slice(self.x, (i, 0), (1, self.x.shape[1]))
        return kernel_matrix(xi, self.x, self.params, kind=self.kind)[0]

    def kernel_diag(self) -> jax.Array:
        """diag(K) (WITHOUT noise) -> (n,); constant s^2 for stationary k."""
        return jnp.full((self.n,), self.params.signal ** 2, dtype=self.x.dtype)

    def dense(self) -> jax.Array:
        """Materialise H = K + sigma^2 I as an (n, n) array (tests only)."""
        return regularised_kernel_matrix(self.x, self.params, kind=self.kind)

    # -- AP block Cholesky cache --------------------------------------------
    def all_block_cholesky(self, block_size: int) -> jax.Array:
        """Cholesky factors of every diagonal block, (nb, b, b).

        Computed once per outer MLL step and cached by the AP solver (paper:
        "the Cholesky factorisation of every block is computed once and
        cached afterwards").
        """
        nb = self.n // block_size
        starts = jnp.arange(nb) * block_size

        def one(start):
            return jnp.linalg.cholesky(self.block(start, block_size))

        return jax.lax.map(one, starts)
