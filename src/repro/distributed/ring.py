"""Distributed ring MVM for the GP path: K(x, x) @ V with rows of x and V
sharded over the whole production mesh.

shard_map implementation: each device holds a row block (x_loc, v_loc). A
rotating copy (x_rot, v_rot) moves around a hierarchical ring — innermost
over the "model" axis, then "data", then "pod" — one `collective_permute`
per step, issued before the local tile contraction so XLA's latency-hiding
scheduler overlaps communication with the Matérn tile GEMMs (DESIGN.md §6).

After `prod(mesh.shape)` steps every device has accumulated
    out_loc = sum_j K(x_loc, x_j) v_j
i.e. the full row block of K @ V. O(n_loc^2 d) compute per step, O(n_loc)
communication; K is never materialised.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.gp.hyperparams import HyperParams
from repro.gp.kernels_math import profile_from_r2, scaled_sqdist

ROW_AXES = ("pod", "data", "model")  # rows sharded over every mesh axis


def _present_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ROW_AXES if a in mesh.shape)


def _rotate(tree, axis_name: str, size: int):
    """ppermute all leaves one step forward along ``axis_name``."""
    perm = [(i, (i + 1) % size) for i in range(size)]
    return jax.tree.map(
        lambda a: jax.lax.ppermute(a, axis_name, perm), tree
    )


def ring_kernel_mvm(
    x: jax.Array,  # (n, d) GLOBAL, row-sharded over all mesh axes
    v: jax.Array,  # (n, s) GLOBAL, row-sharded identically
    params: HyperParams,
    mesh: Mesh,
    kind: str = "matern32",
    tile_dtype=jnp.float32,
) -> jax.Array:
    """K(x, x) @ v on the production mesh (noise NOT added).

    ``tile_dtype=bfloat16`` evaluates the distance/profile tiles in bf16
    with fp32 accumulation (the CG tolerance tau=0.01 is ~1e2 above bf16
    kernel-entry round-off; validated in tests) — halves the dominant
    tile HBM traffic AND puts the cross-term GEMM on the MXU's native
    dtype.
    """
    axes = _present_axes(mesh)
    sizes = [mesh.shape[a] for a in axes]
    profile = profile_from_r2(kind)
    # Constrained hypers enter the manual region as explicit replicated
    # operands (closure capture of sharded tracers is rejected by shard_map).
    lengthscales = params.lengthscales
    signal = params.signal
    # With bf16 tiles, the ROTATING buffers travel the ICI in bf16 too —
    # the ring is compute/ICI balanced at fp32 (measured: 155ms vs 157ms on
    # gp_1m8), so halving rotation bytes moves it firmly compute-bound.
    comm_dtype = tile_dtype

    def local(x_loc, v_loc, ls, sig):
        x_loc_t = (x_loc / ls).astype(tile_dtype)

        # remat: reverse-AD through the ring would otherwise store every
        # (n_loc x n_loc) distance tile — O(devices * tile) HBM. Recompute
        # tiles in the backward sweep instead (they are pure functions of
        # the rotating buffers).
        @jax.checkpoint
        def tile(xr, vr):
            r2 = scaled_sqdist(
                x_loc_t, (xr / ls).astype(tile_dtype), jnp.ones((), tile_dtype)
            )
            k = profile(r2, sig.astype(tile_dtype))
            return jax.lax.dot(
                k, vr.astype(tile_dtype),
                preferred_element_type=jnp.float32,
            )

        def ring_level(level: int, carry):
            """Scan over rotations of mesh axis ``axes[level]``; inner levels
            complete a full sweep between successive rotations."""
            axis = axes[level]
            size = sizes[level]

            def body(c, _):
                acc, xr, vr = c
                if level + 1 < len(axes):
                    acc, xr, vr = ring_level(level + 1, (acc, xr, vr))
                else:
                    acc = acc + tile(xr, vr)
                xr, vr = _rotate((xr, vr), axis, size)
                return (acc, xr, vr), None

            (carry, _) = jax.lax.scan(body, carry, None, length=size)[0], None
            return carry

        acc0 = jnp.zeros((x_loc.shape[0], v_loc.shape[1]), dtype=jnp.float32)
        acc, _, _ = ring_level(
            0, (acc0, x_loc.astype(comm_dtype), v_loc.astype(comm_dtype))
        )
        return acc.astype(v_loc.dtype)

    spec = P(axes, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, P(), P()),
        out_specs=spec,
        check_vma=False,
    )(x, v, lengthscales, signal)


def ring_h_mvm(x, v, params, mesh, kind="matern32", tile_dtype=jnp.float32):
    """H @ v = K @ v + sigma^2 v (distributed)."""
    return ring_kernel_mvm(
        x, v, params, mesh, kind=kind, tile_dtype=tile_dtype
    ) + (params.noise**2) * v


def global_col_norms(r: jax.Array) -> jax.Array:
    """Per-column L2 norms of a row-sharded matrix (works under pjit: the
    reduction is a plain jnp op that XLA turns into cross-device psums)."""
    return jnp.sqrt(jnp.sum(r * r, axis=0))
