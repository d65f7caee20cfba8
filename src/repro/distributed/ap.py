"""Distributed Alternating Projections with PER-SHARD greedy block selection.

The paper's AP (Alg. 2) picks the single globally-worst block per
iteration — a global argmax on the critical path every iteration, which at
512 chips is a straggler/sync hazard. The distributed variant (DESIGN.md
§6) applies the paper's greedy rule WITHIN each shard: every device solves
its own worst local block simultaneously, then the residual is updated
globally with one ring sweep over the (block, delta) pairs.

Semantics: simultaneous disjoint block updates = one sweep of damped block
Jacobi over the selected subset (Gauss-Seidel within a shard's history).
This is NOT the paper's sequential AP: with P shards a fraction P*b/n of
the rows updates at once, and the raw simultaneous update diverges when
those blocks are kernel-coupled (measured: P*b/n = 1/2 on a toy mesh
diverges even at omega=0.3). The implementation therefore applies the
additive-Schwarz safeguard: each shard's correction is scaled by
``omega / P``. For SPD H the additive block-Schwarz operator's spectrum
is bounded by the number of participating subdomains, so the scaled
update converges for any mesh size whenever ``omega < 2`` — robustness
over per-mesh damping tuning, and the price of removing the global-argmax
sync from the critical path. At production scale where coupling is weak
(512 shards, b=1000, n=1.8M, shuffled rows) ``omega`` can be raised
toward ``P`` to recover per-shard step sizes; epoch accounting
(b*devices/n of an epoch per iteration) is unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.ring import _present_axes, _rotate
from repro.gp.hyperparams import HyperParams
from repro.gp.kernels_math import profile_from_r2, scaled_sqdist


def distributed_ap_sweeps(
    x: jax.Array,  # (n, d) row-sharded over all mesh axes
    b_rhs: jax.Array,  # (n, t) row-sharded targets
    v0: jax.Array,  # (n, t) row-sharded warm start
    params: HyperParams,
    mesh: Mesh,
    block_size: int,
    num_iters: int,
    kind: str = "matern32",
    omega: float = 0.3,
) -> tuple[jax.Array, jax.Array]:
    """Run ``num_iters`` per-shard-greedy AP iterations. Returns (v, r)."""
    axes = _present_axes(mesh)
    sizes = [mesh.shape[a] for a in axes]
    num_shards = 1
    for sz in sizes:
        num_shards *= sz
    # Additive-Schwarz safeguard: P simultaneous block corrections can each
    # overshoot along shared kernel-coupled directions; 1/P scaling bounds
    # the combined step (spectral radius < 1 for omega < 2, any mesh).
    omega_eff = omega / num_shards
    profile = profile_from_r2(kind)
    ls, sig = params.lengthscales, params.signal
    noise_var = params.noise**2

    def local(x_loc, b_loc, v_loc):
        n_loc, d = x_loc.shape
        nb = n_loc // block_size

        # Per-block Cholesky cache (paper: factorise once per outer step).
        xb = x_loc.reshape(nb, block_size, d)

        def chol_one(xblk):
            r2 = scaled_sqdist(xblk, xblk, ls)
            h = profile(r2, sig) + noise_var * jnp.eye(block_size)
            return jnp.linalg.cholesky(h)

        chols = jax.lax.map(chol_one, xb)

        def kv_tile(xq, xr, vr):
            r2 = scaled_sqdist(xq, xr, ls)
            return profile(r2, sig) @ vr

        # Initial local residual: r_loc = b_loc - H[loc, :] v  (ring sweep)
        def full_row_mvm(v_in):
            def level(lv, carry):
                axis, size = axes[lv], sizes[lv]

                def body(c, _):
                    acc, xr, vr = c
                    if lv + 1 < len(axes):
                        acc, xr, vr = level(lv + 1, (acc, xr, vr))
                    else:
                        acc = acc + kv_tile(x_loc, xr, vr)
                    xr, vr = _rotate((xr, vr), axis, size)
                    return (acc, xr, vr), None

                return jax.lax.scan(body, carry, None, length=size)[0]

            acc0 = jnp.zeros_like(v_in)
            acc, _, _ = level(0, (acc0, x_loc, v_in))
            return acc + noise_var * v_in

        r = b_loc - full_row_mvm(v_loc)

        def iteration(carry, _):
            v_loc, r = carry
            # Per-shard greedy: worst local block by Frobenius norm.
            blk_norms = jnp.sum(
                r.reshape(nb, block_size, -1) ** 2, axis=(1, 2)
            )
            i = jnp.argmax(blk_norms)
            start = i * block_size
            rb = jax.lax.dynamic_slice(r, (start, 0), (block_size, r.shape[1]))
            delta = omega_eff * jax.scipy.linalg.cho_solve((chols[i], True), rb)
            vb = jax.lax.dynamic_slice(v_loc, (start, 0),
                                       (block_size, v_loc.shape[1]))
            v_loc = jax.lax.dynamic_update_slice(v_loc, vb + delta, (start, 0))

            # Global residual update: every shard's (x_blk, delta) rides the
            # ring once; each device subtracts K(x_loc, x_blk_j) delta_j
            # (+ the local noise term for its own rows).
            x_blk = jax.lax.dynamic_slice(x_loc, (start, 0),
                                          (block_size, x_loc.shape[1]))

            def level(lv, carry):
                axis, size = axes[lv], sizes[lv]

                def body(c, _):
                    upd, xr, dr = c
                    if lv + 1 < len(axes):
                        upd, xr, dr = level(lv + 1, (upd, xr, dr))
                    else:
                        upd = upd + kv_tile(x_loc, xr, dr)
                    xr, dr = _rotate((xr, dr), axis, size)
                    return (upd, xr, dr), None

                return jax.lax.scan(body, carry, None, length=size)[0]

            upd0 = jnp.zeros_like(r)
            upd, _, _ = level(0, (upd0, x_blk, delta))
            # own-block noise contribution
            noise_upd = jnp.zeros_like(r)
            noise_upd = jax.lax.dynamic_update_slice(
                noise_upd, noise_var * delta, (start, 0)
            )
            r = r - upd - noise_upd
            return (v_loc, r), None

        (v_loc, r), _ = jax.lax.scan(
            iteration, (v_loc, r), None, length=num_iters
        )
        return v_loc, r

    spec = P(axes, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec),
        check_vma=False,
    )(x, b_rhs, v0)
