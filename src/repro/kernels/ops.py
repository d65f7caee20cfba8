"""Jit-ready public op around the Pallas kernel MVM, with a custom VJP.

``kernel_mvm(x1, x2, v, params, kind=...)`` computes ``K(x1, x2; theta) @ v``
for any kernel registered in ``repro.kernels.registry`` (RBF and the Matérn
family), with per-dimension lengthscales and signal scale (no noise diagonal
— HOperator adds ``sigma^2 v`` outside). ``kind=None`` defaults to
``params.kernel``.

Differentiation contract: gradients flow to ``x1``, ``x2``, ``v`` and the
hyperparameters. Lengthscale/signal gradients are picked up by plain JAX AD
through the pre-scaling ``u = x / ell`` and the post-scaling ``signal**2 *
out`` — the Pallas pair (forward + backward tile kernels) only ever sees the
unit kernel of pre-scaled inputs, and only the per-tile profile evaluation
differs between kernels. The backward pass is the paper-motivated fusion:
ONE extra sweep over distance tiles serves every hyperparameter.

On the CPU platform the kernels run with ``interpret=True``; on every other
platform the same BlockSpecs compile via Mosaic. The CPU is the only way
into interpret mode: a TPU that failed to start never silently becomes an
interpreted kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.gp.hyperparams import HyperParams, resolve_kind
from repro.kernels.tiled import kernel_mvm_bwd_pallas, kernel_mvm_pallas


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def _pad_rows(a: jax.Array, mult: int) -> jax.Array:
    r = (-a.shape[0]) % mult
    return a if r == 0 else jnp.pad(a, ((0, r), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _unit_mvm(u, w, v, kind, bm, bn, interpret):
    return kernel_mvm_pallas(u, w, v, kind=kind, bm=bm, bn=bn,
                             interpret=interpret)


def _unit_mvm_fwd(u, w, v, kind, bm, bn, interpret):
    return _unit_mvm(u, w, v, kind, bm, bn, interpret), (u, w, v)


def _unit_mvm_bwd(kind, bm, bn, interpret, res, g):
    u, w, v = res
    g = g.astype(jnp.float32)
    # db = kappa(w, u) @ g  — forward kernel, roles swapped.
    dv = kernel_mvm_pallas(w, u, g, kind=kind, bm=bn, bn=bm,
                           interpret=interpret)
    # du: fused distance-tile backward; dw by the (u,w)/(g,v) symmetry
    # D(u,w,g,v)^T = D(w,u,v,g).
    du = kernel_mvm_bwd_pallas(u, w, g, v, kind=kind, bm=bm, bn=bn,
                               interpret=interpret)
    dw = kernel_mvm_bwd_pallas(w, u, v, g, kind=kind, bm=bn, bn=bm,
                               interpret=interpret)
    return du.astype(u.dtype), dw.astype(w.dtype), dv.astype(v.dtype)


_unit_mvm.defvjp(_unit_mvm_fwd, _unit_mvm_bwd)


def kernel_mvm(
    x1: jax.Array,
    x2: jax.Array,
    v: jax.Array,
    params: HyperParams,
    kind: Optional[str] = None,
    bm: int = 256,
    bn: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """K(x1, x2; theta) @ v via the fused Pallas kernel.

    Args:
      x1: (n, d); x2: (m, d); v: (m, s) or (m,).
      kind: registered kernel name; defaults to ``params.kernel``.
    Returns:
      (n, s) or (n,) in x1.dtype.
    """
    kind = resolve_kind(kind, params)
    if interpret is None:
        interpret = _interpret_default()
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    n = x1.shape[0]
    bm = min(bm, max(8, n))
    bn = min(bn, max(8, x2.shape[0]))
    u = _pad_rows(x1 / params.lengthscales, bm)
    w = _pad_rows(x2 / params.lengthscales, bn)
    vp = _pad_rows(v, bn)
    out = _unit_mvm(
        u.astype(jnp.float32), w.astype(jnp.float32), vp.astype(jnp.float32),
        kind, bm, bn, interpret,
    )[:n]
    out = (params.signal**2) * out
    out = out.astype(x1.dtype)
    return out[:, 0] if squeeze else out


def h_mvm(
    x: jax.Array,
    v: jax.Array,
    params: HyperParams,
    kind: Optional[str] = None,
    bm: int = 256,
    bn: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """H_theta @ v = K @ v + sigma^2 v via the Pallas kernel."""
    return kernel_mvm(x, x, v, params, kind=kind, bm=bm, bn=bn,
                      interpret=interpret) + (params.noise**2) * v


def matern_mvm(x1, x2, v, params, bm=256, bn=256, interpret=None):
    """Original Matérn-3/2 entry point (compat wrapper over kernel_mvm)."""
    return kernel_mvm(x1, x2, v, params, kind="matern32", bm=bm, bn=bn,
                      interpret=interpret)
