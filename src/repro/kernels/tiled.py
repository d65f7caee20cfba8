"""Pallas TPU kernels for fused stationary-kernel matrix-vector products.

The GP solvers' hot spot is ``K(x1, x2) @ V`` where ``K`` is n x m and never
fits in HBM for the paper's large-n regime. These kernels stream
FlashAttention-style: a (bm x bn) *distance tile* is built in VMEM from row/
column blocks of the (pre-scaled) inputs — the cross term is a single MXU
GEMM — the kernel profile is applied in VREGs, and the tile is immediately
contracted against the corresponding V block into a (bm x s) fp32
accumulator. K is never materialised.

The tiling plumbing (BlockSpecs, grid order, accumulation, padding contract)
is kernel-AGNOSTIC: the only per-kernel code is the scalar profile
``kappa(r2)`` and its derivative ``dkappa/dr2`` looked up from
``repro.kernels.registry``. Both kernels operate on the UNIT kernel of
PRE-SCALED inputs ``u = x / ell``; the signal**2 factor, lengthscale scaling
and the sigma**2 diagonal live OUTSIDE (ops.py), where plain JAX autodiff
picks up their gradients.

Forward:   out[i]   = sum_j kappa(||u_i - w_j||^2) v_j
Backward:  du_i     = sum_j D_ij * 2 (u_i - w_j),  D = (g v^T) . dkappa/dr2

The same backward kernel computes dw by symmetry (swap (u,w) and (g,v)),
and db is the forward kernel with (u,w) swapped — see ops.py. This is the
"fused hyper-gradient" design from DESIGN.md §4: every hyperparameter's
gradient shares one sweep over distance tiles.

Grid iteration order: grid=(nm, nn) with the column index innermost, so each
(bm x s) output block is revisited consecutively and accumulates in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.registry import MVM_PRECISION, KernelSpec, get_kernel


def _dist_tile(u, w):
    """(bm, bn) squared-distance tile; cross term on the MXU in fp32."""
    uu = jnp.sum(u * u, axis=-1, keepdims=True)  # (bm, 1)
    ww = jnp.sum(w * w, axis=-1, keepdims=True)  # (bn, 1)
    cross = jax.lax.dot_general(
        u, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=MVM_PRECISION,
    )
    return jnp.maximum(uu + ww.T - 2.0 * cross, 0.0)


def _mvm_kernel(spec: KernelSpec, u_ref, w_ref, v_ref, out_ref):
    """One (i, j) tile of kappa(u, w) @ v, accumulated over j."""
    j = pl.program_id(1)
    r2 = _dist_tile(u_ref[...], w_ref[...])
    k = spec.kappa_from_r2(r2)
    acc = jax.lax.dot(
        k.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32,
        precision=MVM_PRECISION,
    )

    @pl.when(j == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += acc


def _mvm_bwd_kernel(spec: KernelSpec, u_ref, w_ref, g_ref, v_ref, du_ref):
    """One (i, j) tile of du = sum_j D_ij 2 (u_i - w_j), accumulated over j.

    D = (g v^T) * dkappa/dr2.
    du_i = 2 * (rowsum(D)_i * u_i - (D @ w)_i).
    """
    j = pl.program_id(1)
    u = u_ref[...]
    w = w_ref[...]
    r2 = _dist_tile(u, w)
    dk = spec.dkappa_dr2(r2)
    e = jax.lax.dot_general(
        g_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=MVM_PRECISION,
    )  # (bm, bn) = g v^T
    d_tile = e * dk
    rowsum = jnp.sum(d_tile, axis=1, keepdims=True)  # (bm, 1)
    dw_contrib = jax.lax.dot(d_tile, w, preferred_element_type=jnp.float32,
                             precision=MVM_PRECISION)
    acc = 2.0 * (rowsum * u - dw_contrib)

    @pl.when(j == 0)
    def _init():
        du_ref[...] = acc

    @pl.when(j > 0)
    def _acc():
        du_ref[...] += acc


def kernel_mvm_pallas(
    u: jax.Array,
    w: jax.Array,
    v: jax.Array,
    *,
    kind: str = "matern32",
    bm: int = 256,
    bn: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """kappa(u, w) @ v for pre-scaled inputs; shapes (n,d),(m,d),(m,s)->(n,s).

    n and m must be multiples of bm / bn (ops.py pads).
    """
    spec = get_kernel(kind)
    n, d = u.shape
    m = w.shape[0]
    s = v.shape[1]
    grid = (n // bm, m // bn)
    return pl.pallas_call(
        functools.partial(_mvm_kernel, spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, s), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, s), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), jnp.float32),
        interpret=interpret,
    )(u, w, v)


def kernel_mvm_bwd_pallas(
    u: jax.Array,
    w: jax.Array,
    g: jax.Array,
    v: jax.Array,
    *,
    kind: str = "matern32",
    bm: int = 256,
    bn: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """du for out = kappa(u, w) @ v with output cotangent g: (n, d)."""
    spec = get_kernel(kind)
    n, d = u.shape
    m = w.shape[0]
    s = v.shape[1]
    grid = (n // bm, m // bn)
    return pl.pallas_call(
        functools.partial(_mvm_bwd_kernel, spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, s), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, s), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(u, w, g, v)


# Matérn-3/2 aliases preserved for the original single-kernel API.
matern_mvm_pallas = functools.partial(kernel_mvm_pallas, kind="matern32")
matern_mvm_bwd_pallas = functools.partial(kernel_mvm_bwd_pallas, kind="matern32")
