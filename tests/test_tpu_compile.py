"""Compile the main-path programs for a described TPU v5e, with no chip.

The TPU compiler is installed alongside JAX, so it can compile for a chip
that is described rather than attached. That catches what interpret mode
cannot: Mosaic refusing a block shape, a kernel over its VMEM budget, or a
program that does not fit the chip's HBM. Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers import every test file. Keep every such test in this one
file. The persistent compilation cache is off while these tests run, since
an entry compiled for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.outer import OuterConfig, init_outer_state, outer_scan
from repro.kernels.tiled import kernel_mvm_bwd_pallas, kernel_mvm_pallas
from repro.solvers import SolverConfig

V5E_HBM_BYTES = 16 * 1024**3
TILE = 1024
N = 12 * TILE  # the pol train split (12,150 rows) padded to whole tiles
S = 65  # 1 + 64 probes
POL_TRAIN = (12_150, 26)
GIB = 1024**3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("d", [3, 26, 90])
def test_pallas_forward_tile_compiles_for_v5e(one_chip, d):
    fn = jax.jit(lambda u, w, v: kernel_mvm_pallas(
        u, w, v, kind="matern32", bm=TILE, bn=TILE, interpret=False))
    hlo = fn.lower(_sds((N, d), one_chip), _sds((N, d), one_chip),
                   _sds((N, S), one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("d", [3, 26, 90])
def test_pallas_backward_tile_compiles_for_v5e(one_chip, d):
    fn = jax.jit(lambda u, w, g, v: kernel_mvm_bwd_pallas(
        u, w, g, v, kind="matern32", bm=TILE, bn=TILE, interpret=False))
    hlo = fn.lower(_sds((N, d), one_chip), _sds((N, d), one_chip),
                   _sds((N, S), one_chip), _sds((N, S), one_chip)
                   ).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_pol_outer_scan_fits_v5e_hbm(one_chip):
    """Two outer steps of the paper's protocol at pol's full size."""
    cfg = OuterConfig(estimator="pathwise", warm_start=True, num_probes=S - 1,
                      num_rff_pairs=1000, solver=SolverConfig(name="cg"),
                      num_steps=2, backend="streamed", bm=TILE, bn=TILE)
    n, d = POL_TRAIN
    x = _sds((n, d), one_chip)
    state = jax.eval_shape(
        lambda k: init_outer_state(k, cfg, jnp.zeros((n, d))),
        jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), state)
    compiled = outer_scan.lower(state, x, _sds((n,), one_chip),
                                cfg=cfg, num_steps=2).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("n, temp_limit", [
    (20 * TILE, GIB),  # the 3droad cell's rows
    (64 * TILE, V5E_HBM_BYTES),  # 3.2 times as many
])
def test_3droad_outer_scan_memory_on_v5e(one_chip, n, temp_limit):
    """Eight budgeted steps at d=3 with the rank-100 preconditioner: the
    gradient recomputes each kernel tile, so nothing O(n^2) is stored."""
    cfg = OuterConfig(estimator="pathwise", warm_start=True, num_probes=S - 1,
                      num_rff_pairs=1000,
                      solver=SolverConfig(name="cg", max_epochs=10.0,
                                          precond_rank=100),
                      num_steps=8, bm=TILE, bn=TILE)
    d = 3
    state = jax.eval_shape(
        lambda k: init_outer_state(k, cfg, jnp.zeros((n, d))),
        jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), state)
    compiled = outer_scan.lower(state, _sds((n, d), one_chip),
                                _sds((n,), one_chip), cfg=cfg,
                                num_steps=8).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_limit, mem
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
