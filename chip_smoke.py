#!/usr/bin/env python3
"""Bring-up smoke of the GP fit-and-serve path on a TPU.

    python chip_smoke.py               # one chip: data, kernel, fit, serve
    python chip_smoke.py --four-chips  # four chips: lane-sharded sweep only

The default run drives the main path once through the library's own entry
points, on the paper's ``pol`` dataset at its published shape (13,500 x 26;
synthetic targets drawn from a GP with a fixed seed, since the UCI file is
not redistributable):

* device: the first JAX device must be a TPU, or the script exits non-zero
  before any work;
* kernel: ``kernels.ops.kernel_mvm`` (Pallas) and the streamed
  ``kernel_mvm_tiled`` at the full train n with a (n, 65) right-hand side,
  for ``matern32`` and ``matern12``, against a blocked dense reference with
  exact distances and ``Precision.HIGHEST`` contractions; the Pallas program
  must contain a Mosaic ``tpu_custom_call`` (not interpret mode);
* fit: ``repro.core.fit`` with the paper's protocol (pathwise estimator,
  warm start, CG at tolerance 0.01, s=64 probes, 1000 RFF pairs, default
  preconditioner) for a few outer steps, once per operator backend; both
  must end at the same hyperparameters to solver-tolerance scale;
* serve: the fitted state exported with ``repro.serve.export_servable``
  into the bucketed engine; every bucket warmed, the test split answered in
  a few batches with zero further compiles.

``--four-chips`` runs only the lane-sharded sweep (``fit_batch`` on a
4-device lane mesh) and compares every lane with the same lane fitted
alone on one chip in the same process.

Any failed check raises, so the exit code is non-zero. On success the last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.runtime import enable_compilation_cache  # noqa: E402

DATASET = "pol"
PUBLISHED_SHAPE = (13_500, 26)
NUM_PROBES = 64  # s: the right-hand side has 1 + s = 65 columns
TILE = 1024  # bm = bn: the OuterConfig default
KERNEL_KINDS = ("matern32", "matern12")
# fp32 accumulation of ~1e4 terms sits near 1e-6 (normwise); one-pass bf16
# products would sit near 1e-3. The bound separates the two.
KERNEL_REL_ERR_BOUND = 1e-4
FIT_STEPS = 4
CG_TOLERANCE = 0.01
# Both backends solve the same systems from the same probes; their
# hyperparameters may differ only by what the solver tolerance allows.
BACKEND_HYPERS_RTOL = 1e-2
SERVE_BUCKETS = (16, 64, 256)
SERVE_BATCHES = (1, 16, 50, 64, 200, 256)
RMSE_BOUND = 1.0  # y is standardised: 1.0 is the predict-zero baseline
# --four-chips: tolerance x seed lanes of one sweep, one lane per chip.
SWEEP_TOLERANCES = (0.01, 0.05)
SWEEP_SEEDS = (0, 1)
SWEEP_STEPS = 3
# A chip that ran a lane peaks at this many times the replicated dataset.
LANE_PEAK_FACTOR = 10


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(need: int) -> dict:
    """The first device must be a TPU and there must be ``need`` of them."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if dev.platform != "tpu":
        raise SystemExit(f"[device] FAIL: no TPU (JAX platform is "
                         f"{dev.platform!r}); nothing was run")
    if len(devices) < need:
        raise SystemExit(f"[device] FAIL: need {need} TPU chips, JAX sees "
                         f"{len(devices)}")
    return info


def load_data():
    """``pol`` at its published shape, from the seeded synthetic generator."""
    from repro.data.synthetic import UCI_SHAPES, load_dataset

    uci_dir = os.path.join(REPO, "data", "uci")
    csv = os.path.join(uci_dir, f"{DATASET}.csv")
    if os.path.exists(csv):
        raise SystemExit(f"[data] FAIL: {csv} exists; the smoke runs on the "
                         "seeded synthetic set only")
    assert UCI_SHAPES[DATASET] == PUBLISHED_SHAPE
    t0 = time.perf_counter()
    ds = load_dataset(DATASET, uci_dir=uci_dir)
    n, d = PUBLISHED_SHAPE
    n_train = int(0.9 * n)
    assert ds.x_train.shape == (n_train, d), ds.x_train.shape
    assert ds.x_test.shape == (n - n_train, d), ds.x_test.shape
    log(f"[data] {DATASET}: seeded synthetic GP draw at the published shape "
        f"{n}x{d} (train {n_train}, test {n - n_train}), "
        f"{time.perf_counter() - t0:.1f}s")
    return ds


def dense_reference(x, v, params, block: int = 128):
    """K(x, x) @ v by row blocks with exact (difference-form) distances and
    ``Precision.HIGHEST`` contractions: the plain reference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.registry import get_kernel

    spec = get_kernel(params.kernel)
    n, d = x.shape
    u = x / params.lengthscales
    nb = -(-n // block)
    blocks = jnp.pad(u, ((0, nb * block - n), (0, 0))).reshape(nb, block, d)

    def one(ub):
        r2 = jnp.sum((ub[:, None, :] - u[None, :, :]) ** 2, axis=-1)
        return jnp.matmul(spec.kappa_from_r2(r2), v,
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(one, blocks).reshape(nb * block, -1)[:n]
    return params.signal ** 2 * out


def rel_err(out, ref) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def kernel_phase(x) -> dict:
    """Pallas and streamed kernel MVMs against the dense reference."""
    import jax
    import jax.numpy as jnp

    from repro.gp.hyperparams import HyperParams
    from repro.kernels.ops import kernel_mvm
    from repro.solvers.operator import kernel_mvm_tiled

    n, d = x.shape
    v = jax.random.normal(jax.random.PRNGKey(1), (n, 1 + NUM_PROBES), x.dtype)
    pallas = jax.jit(lambda x, v, p: kernel_mvm(x, x, v, p, bm=TILE, bn=TILE))
    streamed = jax.jit(
        lambda x, v, p: kernel_mvm_tiled(x, x, v, p, bm=TILE, bn=TILE))
    reference = jax.jit(dense_reference)
    errors = {}
    for kind in KERNEL_KINDS:
        # Lengthscale sqrt(d) on standardised inputs puts typical scaled
        # distances near 1, so K is far from diagonal and every entry counts.
        params = HyperParams.create(d, lengthscale=float(d) ** 0.5,
                                    kernel=kind)
        hlo = pallas.lower(x, v, params).compile().as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError(f"[kernel] {kind}: the Pallas program has no "
                                 "tpu_custom_call (interpret mode?)")
        ref = jax.block_until_ready(reference(x, v, params))
        for name, fn in (("pallas", pallas), ("streamed", streamed)):
            out = jax.block_until_ready(fn(x, v, params))
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(x, v, params))
            dt = time.perf_counter() - t0
            if not bool(jnp.all(jnp.isfinite(out))):
                raise AssertionError(f"[kernel] {kind}/{name}: non-finite")
            err = rel_err(out, ref)
            errors[f"{kind}/{name}"] = err
            log(f"[kernel] {kind} {name}: n={n} s={1 + NUM_PROBES} "
                f"max rel err vs HIGHEST reference {err:.3e} "
                f"(bound {KERNEL_REL_ERR_BOUND:.0e}); one call {dt * 1e3:.1f} "
                "ms (host clock)")
    bad = {k: e for k, e in errors.items() if not e <= KERNEL_REL_ERR_BOUND}
    if bad:
        raise AssertionError(f"[kernel] FAIL: errors above "
                             f"{KERNEL_REL_ERR_BOUND:.0e}: {bad}")
    log("[kernel] ok: tpu_custom_call present, all paths within bound")
    return errors


def fit_config(backend: str, num_steps: int = FIT_STEPS):
    from repro.core import OuterConfig
    from repro.solvers import SolverConfig

    return OuterConfig(
        estimator="pathwise", warm_start=True, num_probes=NUM_PROBES,
        num_rff_pairs=1000, solver=SolverConfig(name="cg",
                                                tolerance=CG_TOLERANCE),
        num_steps=num_steps, backend=backend, bm=TILE, bn=TILE,
    )


def _check_fit(tag: str, res, tolerance: float) -> None:
    import numpy as np

    h = res.history
    for name in ("res_y", "res_z", "hypers"):
        if not np.all(np.isfinite(h[name])):
            raise AssertionError(f"[{tag}] non-finite {name}: {h[name]}")
    worst = float(np.max(np.maximum(h["res_y"], h["res_z"])))
    if worst > tolerance:
        raise AssertionError(f"[{tag}] a solve stopped above tolerance "
                             f"{tolerance}: residual {worst:.3e}")


def fit_phase(ds) -> dict:
    """The paper's fit protocol on both operator backends."""
    import jax
    import numpy as np

    from repro.core import fit

    results = {}
    for backend in ("streamed", "pallas"):
        cfg = fit_config(backend)
        res = fit(ds.x_train, ds.y_train, cfg, key=jax.random.PRNGKey(0))
        h = res.history
        for i in range(cfg.num_steps):
            log(f"[fit] {backend} step {i + 1}/{cfg.num_steps}: "
                f"res_y={h['res_y'][i]:.3e} res_z={h['res_z'][i]:.3e} "
                f"iters={int(h['iters'][i])} epochs={h['epochs'][i]:.1f}")
        log(f"[fit] {backend}: wall {res.wall_time_s:.1f}s for "
            f"{cfg.num_steps} steps, compile included (host clock)")
        _check_fit(f"fit {backend}", res, CG_TOLERANCE)
        results[backend] = res
    a = results["streamed"].history["hypers"][-1]
    b = results["pallas"].history["hypers"][-1]
    diff = float(np.max(np.abs(a - b) / np.abs(a)))
    log(f"[fit] final hypers streamed vs pallas: max rel diff {diff:.3e} "
        f"(bound {BACKEND_HYPERS_RTOL:.0e})")
    if not diff <= BACKEND_HYPERS_RTOL:
        raise AssertionError(f"[fit] FAIL: backends disagree: {a} vs {b}")
    log("[fit] ok")
    return results


def serve_phase(ds, state) -> float:
    """Export the fit into the bucketed engine and answer the test split."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pathwise_predict
    from repro.serve import BucketedEngine, export_servable

    model = export_servable(state, ds.x_train)
    engine = BucketedEngine(model, buckets=SERVE_BUCKETS, bm=TILE, bn=TILE)
    t0 = time.perf_counter()
    compiles = engine.warmup()
    log(f"[serve] warmed buckets {SERVE_BUCKETS}: {compiles} executables, "
        f"{time.perf_counter() - t0:.1f}s (host clock)")
    if compiles is None:
        raise AssertionError("[serve] num_compiles() is None: the retrace "
                             "check cannot run")
    x_test, y_test = ds.x_test, ds.y_test
    lo = 0
    for m in SERVE_BATCHES:
        xq = x_test[lo:lo + m]
        t0 = time.perf_counter()
        pred = engine.submit(xq)
        mean = np.asarray(pred.mean)
        var = np.asarray(pred.var)
        dt = time.perf_counter() - t0
        if mean.shape != (m,) or var.shape != (m,):
            raise AssertionError(f"[serve] batch {m}: shapes {mean.shape}, "
                                 f"{var.shape}")
        if not (np.all(np.isfinite(mean)) and np.all(np.isfinite(var))
                and np.all(var > 0)):
            raise AssertionError(f"[serve] batch {m}: non-finite or "
                                 "non-positive predictions")
        log(f"[serve] batch of {m}: ok, {dt * 1e3:.1f} ms (host clock)")
        lo += m
    # The engine pads to buckets; the direct call does not. Same answers.
    xq = x_test[:SERVE_BUCKETS[1]]
    direct = pathwise_predict(ds.x_train, xq, state.carry_v, state.probes,
                              state.params, bm=TILE, bn=TILE)
    diff = float(jnp.max(jnp.abs(engine.submit(xq).mean - direct.mean))
                 / jnp.max(jnp.abs(direct.mean)))
    if not diff <= KERNEL_REL_ERR_BOUND:
        raise AssertionError(f"[serve] engine vs direct predict: {diff:.3e}")
    full = engine.submit(x_test)  # larger than any bucket: chunked
    rmse = float(jnp.sqrt(jnp.mean((full.mean - y_test) ** 2)))
    now = engine.num_compiles()
    log(f"[serve] test split {x_test.shape[0]} rows: RMSE {rmse:.4f} "
        f"(bound {RMSE_BOUND}); engine vs direct predict {diff:.3e}; "
        f"compiles after warmup {compiles} -> {now}")
    if not rmse < RMSE_BOUND:
        raise AssertionError(f"[serve] FAIL: test RMSE {rmse:.4f}")
    if now is None or now != compiles:
        raise AssertionError(f"[serve] FAIL: engine compiled after warmup "
                             f"({compiles} -> {now})")
    log("[serve] ok")
    return rmse


def four_chip_phase(ds) -> None:
    """Lane-sharded sweep on a 4-chip lane mesh vs each lane alone."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fit, fit_batch
    from repro.launch.mesh import make_lane_mesh
    from repro.solvers import numerics_of, stack_numerics

    mesh = make_lane_mesh()
    if mesh.devices.size != 4:
        raise AssertionError(f"[sweep] lane mesh has {mesh.devices.size} "
                             "devices, want 4")
    cfg = fit_config("streamed", num_steps=SWEEP_STEPS)
    lanes = [(t, s) for t in SWEEP_TOLERANCES for s in SWEEP_SEEDS]
    nums = [numerics_of(dataclasses.replace(cfg.solver, tolerance=t))
            for t, _ in lanes]
    keys = jnp.stack([jax.random.PRNGKey(s) for _, s in lanes])
    x, y = ds.x_train, ds.y_train

    t0 = time.perf_counter()
    sharded = fit_batch(x, y, cfg, keys, numerics=stack_numerics(nums),
                        mesh=mesh)
    log(f"[sweep] {len(lanes)} lanes (tolerance x seed) on a "
        f"{mesh.devices.size}-chip lane mesh: {time.perf_counter() - t0:.1f}s"
        " compile included (host clock)")
    # Every chip must have run a lane. A chip that only received the
    # replicated dataset peaks near its size; one that ran a lane also held
    # that lane's carry, probes and kernel tiles, tens of dataset sizes.
    data_bytes = x.nbytes + y.nbytes
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in mesh.devices.flat]
    log(f"[sweep] peak bytes per chip {peaks} (dataset {data_bytes})")
    if min(peaks) < LANE_PEAK_FACTOR * data_bytes:
        raise AssertionError(f"[sweep] FAIL: a chip ran no lane: {peaks}")

    for i, ((tol, seed), num) in enumerate(zip(lanes, nums)):
        alone = fit(x, y, cfg, key=jax.random.PRNGKey(seed), numerics=num)
        _check_fit(f"sweep lane {i}", alone, tol)
        _check_fit(f"sweep lane {i}", sharded[i], tol)
        a, b = alone.history, sharded[i].history
        iters_equal = np.array_equal(a["iters"], b["iters"])
        hyp = float(np.max(np.abs(a["hypers"] - b["hypers"])
                           / np.abs(a["hypers"])))
        log(f"[sweep] lane {i} tol={tol} seed={seed}: iters alone "
            f"{a['iters'].tolist()} sharded {b['iters'].tolist()}; hypers "
            f"max rel diff {hyp:.3e}")
        if not iters_equal:
            raise AssertionError(f"[sweep] FAIL: lane {i} iterations differ")
        if not hyp <= KERNEL_REL_ERR_BOUND:
            raise AssertionError(f"[sweep] FAIL: lane {i} hypers differ "
                                 f"by {hyp:.3e}")
    log("[sweep] ok: every lane matches its single-chip fit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded sweep on four chips")
    args = ap.parse_args(argv)
    cache = enable_compilation_cache()
    need = 4 if args.four_chips else 1
    device = device_check(need)
    log(f"[cache] persistent compilation cache: {cache}")
    ds = load_data()
    if args.four_chips:
        four_chip_phase(ds)
    else:
        kernel_phase(ds.x_train)
        fits = fit_phase(ds)
        serve_phase(ds, fits["pallas"].state)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
