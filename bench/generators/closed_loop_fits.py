"""Traffic generator: a closed loop of back-to-back hyperparameter fits.

Each fit is one call of ``repro.core.fit`` as a user makes it: the
configuration's ``OuterConfig`` and initial hyperparameters, the library's
default operator backend and chunking, and a key of its own. A fit starts
when the previous one has returned. The window opens at the first timed fit
and closes at the end of the last fit that the previous fit's duration says
will end within ``seconds``; the first fit always runs.

The fits' keys are the same in every run (fit i's key is drawn from the
traffic's ``key_seed`` and i), as is the configuration's dataset; the run's
seed orders the dataset's rows (``bench.data``). A fit's work, its solver
iterations, follows from its data and probes, so every seed is given the
same work.

Set-up draws the data on the device and runs, from a fresh state, each
chunk length of ``outer_scan`` that ``fit`` will run (its default
``steps_per_round`` over the configuration's outer steps), so that every
program a fit runs is compiled, or loaded from the persistent cache, before
the window.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``max_epochs``: the solver's epoch budget per outer step, or null for
  the library's default (no budget: solve to the configuration's
  tolerance);
* ``key_seed``: the fixed seed of the fits' keys;
* ``max_fits``: how many fit keys are drawn ahead of the window (a bound
  on the fits one window can hold).
"""
from __future__ import annotations

import inspect
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from bench import check
from bench.data import data_for, seed_key

WINDOW_STREAM = 1
WARMUP_STREAM = 2


@dataclass
class Prepared:
    """What set-up leaves for the window."""

    x: jax.Array
    y: jax.Array
    cfg: object  # repro.core.OuterConfig
    init_params: object  # repro.gp.hyperparams.HyperParams
    fit_keys: np.ndarray  # (max_fits, 2) uint32
    config: dict


@dataclass
class Window:
    """What the window did."""

    seconds: float = 0.0
    steps: int = 0
    attempted: int = 0
    failed: int = 0
    histories: list = field(default_factory=list)
    # What the check reads of each fit (check.FitOutput); the rest of its
    # state is dropped.
    outputs: list = field(default_factory=list)


def outer_config(config: dict, traffic: dict):
    """The library config a user would build for this deployment."""
    from repro.core import OuterConfig
    from repro.solvers import SolverConfig
    from repro.train.adam import AdamConfig

    solver = dict(config["solver"])
    if traffic["max_epochs"] is not None:
        solver["max_epochs"] = float(traffic["max_epochs"])
    return OuterConfig(
        estimator=config["estimator"],
        warm_start=config["warm_start"],
        num_probes=config["num_probes"],
        num_rff_pairs=config["num_rff_pairs"],
        kind=config["kernel"],
        solver=SolverConfig(**solver),
        adam=AdamConfig(learning_rate=config["adam_learning_rate"],
                        b1=config["adam_betas"][0], b2=config["adam_betas"][1],
                        eps=config["adam_eps"]),
        num_steps=config["outer_steps"],
        bm=config["tile"]["bm"], bn=config["tile"]["bn"],
    )


def _init_params(config: dict):
    from repro.gp.hyperparams import HyperParams

    init = config["init"]
    return HyperParams.create(int(config["d"]),
                              lengthscale=init["lengthscale"],
                              signal=init["signal"], noise=init["noise"],
                              kernel=config["kernel"])


def _keys(seed: int, stream: int, count: int) -> np.ndarray:
    base = jax.random.fold_in(seed_key(seed), stream)
    idx = jax.numpy.arange(count)
    return np.asarray(jax.vmap(lambda i: jax.random.fold_in(base, i))(idx))


def chunk_lengths(num_steps: int) -> set[int]:
    """The ``outer_scan`` lengths ``fit`` runs for ``num_steps`` steps."""
    from repro.core import fit

    per_round = inspect.signature(fit).parameters["steps_per_round"].default
    if per_round <= 0 or per_round >= num_steps:
        return {num_steps}
    return {per_round} | ({num_steps % per_round} - {0})


def warm_up(prep: "Prepared", key) -> None:
    """Run each chunk length once, from a fresh state, as ``fit`` calls it."""
    from repro.core import init_outer_state, outer_scan

    for k in sorted(chunk_lengths(prep.cfg.num_steps)):
        state = init_outer_state(key, prep.cfg, prep.x,
                                 init_params=prep.init_params)
        state, _ = outer_scan(state, prep.x, prep.y, prep.cfg, k,
                              numerics=None)
        jax.block_until_ready(state.carry_v)


def setup(cell, seed: int) -> Prepared:
    """Data on the device, the library config and the fits' keys, with
    every program of a fit warmed up."""
    x, y = jax.block_until_ready(data_for(cell.config, seed))
    keys = _keys(cell.traffic["key_seed"], WINDOW_STREAM,
                 int(cell.traffic["max_fits"]))
    prep = Prepared(x=x, y=y, cfg=outer_config(cell.config, cell.traffic),
                    init_params=_init_params(cell.config), fit_keys=keys,
                    config=cell.config)
    warm_up(prep, _keys(cell.traffic["key_seed"], WARMUP_STREAM, 1)[0])
    return prep


def program_temp_bytes(prep: Prepared) -> int:
    """The most temporary device memory that one of the window's programs
    (``outer_scan`` at each chunk length ``fit`` runs) asks for, from its
    compiled memory analysis: the allocator's peak does not count it."""
    from repro.core import init_outer_state, outer_scan

    state = init_outer_state(prep.fit_keys[0], prep.cfg, prep.x,
                             init_params=prep.init_params)
    return max(int(outer_scan.lower(state, prep.x, prep.y, prep.cfg, k)
                   .compile().memory_analysis().temp_size_in_bytes)
               for k in chunk_lengths(prep.cfg.num_steps))


def window_programs(cell) -> list:
    """``outer_scan`` at each chunk length a fit of the cell runs, lowered
    for the cell's shapes as the window runs it."""
    import jax.numpy as jnp

    from repro.core import init_outer_state, outer_scan

    cfg = outer_config(cell.config, cell.traffic)
    params = _init_params(cell.config)
    n, d = int(cell.config["n_train"]), int(cell.config["d"])
    x = jax.ShapeDtypeStruct((n, d), jnp.float32)
    y = jax.ShapeDtypeStruct((n,), jnp.float32)
    state = jax.eval_shape(
        lambda key: init_outer_state(key, cfg, x, init_params=params),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return [outer_scan.lower(state, x, y, cfg, k)
            for k in sorted(chunk_lengths(cfg.num_steps))]


def _output(res) -> check.FitOutput:
    probes, adam = res.state.probes, res.state.adam

    def flat(p):
        return np.concatenate([np.ravel(p.raw_lengthscales),
                               np.ravel(p.raw_signal), np.ravel(p.raw_noise)])

    return check.FitOutput(
        history=res.history, carry_v=res.state.carry_v,
        rff_z=probes.rff.z, rff_u=probes.rff.u, rff_w=probes.rff.w,
        w_eps=probes.w_eps, adam_mu=flat(adam.mu), adam_nu=flat(adam.nu))


def _finite(hist: dict) -> bool:
    return all(np.all(np.isfinite(hist[k]))
               for k in ("res_y", "res_z", "hypers", "grad_norm"))


def measure(prep: Prepared, seconds: float) -> Window:
    """Back-to-back fits for about ``seconds``."""
    from repro.core import fit

    win = Window()
    last = 0.0
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for key in prep.fit_keys:
            elapsed = time.perf_counter() - t_start
            if win.attempted and elapsed + last > seconds:
                break
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.fit"):
                res = fit(prep.x, prep.y, prep.cfg, key=key,
                          init_params=prep.init_params)
                jax.block_until_ready(res.state.carry_v)
            last = time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("bench.between_fits"):
                win.attempted += 1
                win.steps += len(res.history["iters"])
                win.failed += not _finite(res.history)
                win.histories.append(res.history)
                win.outputs.append(_output(res))
    win.seconds = time.perf_counter() - t_start
    return win


def compare(prep: Prepared, win: Window, limits: dict):
    """The check of every fit the window completed (see bench.check)."""
    return check.compare(prep.x, prep.y, prep.config, win.outputs, limits)
