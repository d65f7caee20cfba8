"""Production mesh builders (spec: MULTI-POD DRY-RUN step 1).

Functions, not module-level constants — importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with ``Auto`` axes: shardings propagate through jit
    as in ``NamedSharding`` placement, instead of the ``Explicit`` default
    that types every intermediate and rejects gathers without an
    ``out_sharding``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = 256 chips/pod single-pod; (2, 16, 16) = 512 chips 2-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (smoke tests: 1 CPU device)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def make_lane_mesh(num_devices: int | None = None):
    """1-D mesh over a ``"lanes"`` axis for data-parallel scenario sweeps.

    Each device owns a contiguous slice of the vmap lane axis of a batched
    sweep (``core.driver.fit_batch(mesh=...)``): lanes are embarrassingly
    parallel, so a ``NamedSharding`` over this mesh turns the one-program
    grid into one program PER DEVICE worth of lanes with no collectives on
    the hot path. Defaults to every local device; CPU tests force virtual
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    n = num_devices or len(jax.devices())
    return _auto_mesh((n,), ("lanes",))


# TPU v5e hardware model for the roofline (per chip).
PEAK_BF16_FLOPS = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
