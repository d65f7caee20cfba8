"""Training driver for iterative-GP marginal-likelihood optimisation.

Python-level loop around the jitted `outer_step`: metrics capture, periodic
evaluation via pathwise conditioning, SGD learning-rate grid search (paper
Appendix B protocol), the large-dataset hyperparameter-initialisation
heuristic, and checkpoint/restart.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estimators import PATHWISE, build_system_targets, init_probes
from repro.core.outer import (
    OuterConfig,
    OuterState,
    _require_history,
    effective_kind,
    init_outer_state,
    init_outer_state_lanes,
    num_lanes,
    outer_scan,
    unstack_state,
)
from repro.core.predict import pathwise_predict, predictive_metrics
from repro.distributed.checkpoint import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.gp.hyperparams import HyperParams
from repro.obs import scopes
from repro.obs.trace import span
from repro.solvers import (
    HOperator,
    SolverNumerics,
    broadcast_numerics,
    solve,
)
from repro.solvers.adaptive import (
    BudgetPolicy,
    broadcast_policy,
    resolve_horizon,
)
from repro.train.adam import AdamConfig, adam_init, adam_update

SGD_LR_GRID = [5.0, 10.0, 20.0, 30.0, 50.0, 60.0, 70.0, 80.0, 90.0, 100.0]

# Divergence cut-off for the SGD learning-rate grid search (paper Appendix B:
# "the largest learning rate which does not cause divergence"). Systems are
# normalised to ||b~|| = 1 (solvers.base), so a cold-started probe solve
# begins at relative residual ~1 per system family; after the probe epochs,
# res_y + res_z above 2 + 2 means BOTH families grew past twice their
# starting norm — the iteration is expanding, not contracting.
SGD_DIVERGENCE_THRESHOLD = 4.0


@dataclass
class FitResult:
    """What `fit`/`fit_batch` return: final state + per-step history."""

    state: OuterState
    history: dict  # str -> np.ndarray over steps
    wall_time_s: float


def pick_sgd_learning_rate(
    x: jax.Array,
    y: jax.Array,
    params: HyperParams,
    cfg: OuterConfig,
    key: jax.Array,
    grid=None,
    probe_epochs: float = 3.0,
    halve: bool = False,
    divergence_threshold: float = SGD_DIVERGENCE_THRESHOLD,
) -> float:
    """Paper protocol: largest grid lr whose first-step solve does not
    diverge; ``halve=True`` returns half of it (large-dataset rule).
    "Diverged" means ``res_y + res_z`` is non-finite or exceeds
    ``divergence_threshold`` (see :data:`SGD_DIVERGENCE_THRESHOLD`),
    evaluated on the FINAL probe residual (paper protocol) — the threshold
    is deliberately NOT baked into the probe solver config, because
    freezing at the first crossing would reject learning rates whose noisy
    early residual estimate transiently overshoots but recovers within the
    probe budget."""
    grid = sorted(grid or SGD_LR_GRID)
    n, d = x.shape
    kind = effective_kind(cfg, params)
    probes = init_probes(
        key, cfg.estimator, n, d, cfg.num_probes, cfg.num_rff_pairs,
        kind=kind, dtype=x.dtype,
    )
    targets = build_system_targets(probes, x, y, params)
    op = HOperator(x=x, params=params, kind=kind, backend=cfg.backend,
                   bm=cfg.bm, bn=cfg.bn)
    best = grid[0]
    for lr in grid:
        # Pin the probe's divergence freeze OFF even if the caller's config
        # sets one: the decision must read the FINAL residual (see above).
        scfg = replace(cfg.solver, name="sgd", learning_rate=lr,
                       max_epochs=probe_epochs, kind=kind,
                       divergence_threshold=float("inf"))
        res = solve(op, targets, None, scfg, key=key)
        r = float(res.res_y) + float(res.res_z)
        if np.isfinite(r) and r < divergence_threshold:
            best = lr
        else:
            break
    return best / 2.0 if halve else best


def init_hypers_heuristic(
    key: jax.Array,
    x: jax.Array,
    y: jax.Array,
    subset_size: int = 10_000,
    num_centroids: int = 10,
    num_steps: int = 30,
    adam_lr: float = 0.1,
    kind: str = "matern32",
) -> HyperParams:
    """Large-dataset initialisation heuristic (paper Appendix B / Lin et al.):

    repeat ``num_centroids`` times: pick a random centroid, take its
    ``subset_size`` nearest neighbours, maximise the EXACT subset MLL;
    average the resulting hyperparameters (in raw space).
    """
    from repro.gp.exact import exact_mll

    n, d = x.shape
    subset_size = min(subset_size, n)
    keys = jax.random.split(key, num_centroids)
    acc = None

    @jax.jit
    def subset_fit(xc, yc):
        params = HyperParams.create(d, dtype=x.dtype, kernel=kind)
        adam = adam_init(params)
        cfg = AdamConfig(learning_rate=adam_lr)

        def body(carry, _):
            p, a = carry
            g = jax.grad(lambda q: exact_mll(xc, yc, q, kind=kind))(p)
            p, a = adam_update(g, a, p, cfg, maximize=True)
            return (p, a), None

        (params, _), _ = jax.lax.scan(body, (params, adam), None, length=num_steps)
        return params

    for k in keys:
        i = jax.random.randint(k, (), 0, n)
        dist = jnp.sum((x - x[i]) ** 2, axis=1)
        idx = jnp.argsort(dist)[:subset_size]
        p = subset_fit(x[idx], y[idx])
        acc = p if acc is None else jax.tree.map(jnp.add, acc, p)
    return jax.tree.map(lambda v: v / num_centroids, acc)


def _empty_history() -> dict[str, list]:
    return {
        "res_y": [], "res_z": [], "iters": [], "epochs": [],
        "hypers": [], "grad_norm": [], "data_fit": [],
        "eval_step": [], "eval_rmse": [], "eval_llh": [],
        "step_time_s": [],
    }


def _round_size(step: int, num_steps: int, steps_per_round: int,
                *boundaries: int) -> int:
    """Steps to scan this round: capped by ``steps_per_round`` (<= 0 means
    "all remaining") and never crossing an eval/checkpoint boundary."""
    k = num_steps - step
    if steps_per_round > 0:
        k = min(k, steps_per_round)
    for every in boundaries:
        if every:
            k = min(k, every - step % every)
    return k


def _append_round(history: dict, metrics: dict, dt: float, k: int,
                  lane: Optional[int] = None,
                  event_log=None, solver: str = "") -> None:
    """Append one scan round's stacked metrics (leading axis = k steps) to
    the per-step history lists.

    The round's time splits into solve, gradient and the rest only on the
    device's clock: a profile of the fit reads it from the ``gp.*`` scopes
    (``repro.obs.scopes``, ``docs/observability.md``).

    When ``event_log`` (a :class:`repro.obs.trace.EventLog`) is given, one
    structured ``solve_step`` event is emitted per outer step — the host-side
    aggregation point for the solvers' in-loop telemetry. When the solver
    recorded residual rings (``SolverConfig.record_history``), the metrics
    carry ``res_history`` and each event (and the history dict) gets the
    step's time-ordered residual trajectory.

    Under an adaptive budget (``fit(budget_policy=...)``) the metrics carry
    the ``budget_*`` family; those columns join the history dict and each
    step additionally emits a ``budget_decision`` event — predicted vs
    realised epochs plus the controller's calibrated state (schema:
    ``docs/adaptive.md``).
    """
    def col(name, dtype=float):
        a = np.asarray(metrics[name])
        return np.asarray(a[:, lane] if lane is not None else a, dtype=dtype)

    epochs = col("epochs", np.float64)
    steps = col("step", int)
    iters = col("iters", int)
    res_y, res_z = col("res_y"), col("res_z")
    history["res_y"].extend(res_y)
    history["res_z"].extend(res_z)
    history["iters"].extend(iters)
    history["epochs"].extend(epochs)
    history["hypers"].extend(col("hypers", None))
    history["grad_norm"].extend(col("grad_norm"))
    history["data_fit"].extend(col("data_fit"))
    history["step_time_s"].extend([dt / k] * k)
    rings = None
    if "res_history" in metrics:
        from repro.solvers.base import unroll_history

        a = np.asarray(metrics["res_history"])
        a = a[:, lane] if lane is not None else a  # (k, H, 2)
        rings = np.stack([unroll_history(h, i) for h, i in zip(a, iters)])
        history.setdefault("res_history", []).extend(rings)
    budget_cols = {
        name: col(name) for name in metrics if name.startswith("budget_")
    }
    for name, vals in budget_cols.items():
        history.setdefault(name, []).extend(vals)
    if event_log is not None:
        for j in range(k):
            fields = dict(
                step=int(steps[j]), solver=solver, lane=lane,
                res_y=float(res_y[j]), res_z=float(res_z[j]),
                iters=int(iters[j]), epochs=float(epochs[j]),
                step_time_s=dt / k,
            )
            if rings is not None:
                row = rings[j]
                fields["res_history"] = row[np.isfinite(row[:, 0])].tolist()
            event_log.emit("solve_step", **fields)
            if budget_cols:
                event_log.emit("budget_decision", step=int(steps[j]),
                               solver=solver, lane=lane, **{
                                   name[len("budget_"):]: float(vals[j])
                                   for name, vals in budget_cols.items()
                               })


def fit(
    x: jax.Array,
    y: jax.Array,
    cfg: OuterConfig,
    key: Optional[jax.Array] = None,
    init_params: Optional[HyperParams] = None,
    x_test: Optional[jax.Array] = None,
    y_test: Optional[jax.Array] = None,
    eval_every: int = 0,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    resume: bool = True,
    verbose: bool = False,
    steps_per_round: int = 8,
    numerics: Optional[SolverNumerics] = None,
    event_log=None,
    budget_policy: Optional[BudgetPolicy] = None,
) -> FitResult:
    """Run ``cfg.num_steps`` outer MLL steps with optional eval/checkpointing.

    The outer loop runs in scan chunks of up to ``steps_per_round`` steps
    (:func:`repro.core.outer.outer_scan`): one device dispatch and one host
    sync per round instead of per step. Chunks never cross an eval or
    checkpoint boundary, and the scan body is the same traced computation
    as :func:`outer_step`, so the trajectory is independent of the chunking
    (``steps_per_round=1`` reproduces the legacy per-step loop exactly;
    ``<= 0`` scans all remaining steps in one dispatch).

    Compile-cost note: each distinct chunk length is a separate
    ``outer_scan`` executable (``num_steps`` is static). Aligned cadences —
    no boundaries, or ``eval_every``/``ckpt_every`` multiples of
    ``steps_per_round`` — use one or two; pathological co-prime cadences
    can produce one per distinct remainder, so align them when compile
    time matters.

    Restart semantics: if ``ckpt_dir`` holds a checkpoint and ``resume``,
    training continues from it — including warm-start carry and probe draws,
    so solver progress survives preemption (DESIGN.md §6).

    ``numerics`` (a scalar-leaf :class:`SolverNumerics`) overrides the
    numeric solver settings as TRACED values: runs differing only in
    tolerance/budget/lr share one executable (same maths as baking them
    into ``cfg.solver``).

    ``event_log`` (a :class:`repro.obs.trace.EventLog`) turns on structured
    telemetry: one ``solve_step`` JSONL event per outer step (residuals,
    iteration/epoch counts, per-step residual trajectory when
    ``cfg.solver.record_history`` is on) plus a final ``fit_done`` summary —
    wall-clock-free ground truth for convergence-ordering assertions.

    ``budget_policy`` (a scalar-leaf
    :class:`repro.solvers.adaptive.BudgetPolicy`, see
    ``make_budget_policy``) turns on the adaptive budget controller: each
    step's ``max_epochs`` becomes the controller's traced allocation,
    calibrated online from the solver residual rings — which requires
    ``cfg.solver.record_history >= 2`` (raises ``ValueError`` otherwise).
    An :data:`~repro.solvers.adaptive.AUTO_HORIZON` horizon is resolved to
    ``cfg.num_steps`` here. History gains the ``budget_*`` columns and
    ``event_log`` a per-step ``budget_decision`` event; ``None`` (default)
    keeps ``fit`` bit-identical to the fixed-budget behaviour.

    The driver's phases are ``fit.*`` spans (``repro.obs.scopes``):
    ``fit.init``, one ``fit.chunk`` per scan round (``steps=k``),
    ``fit.metrics``, ``fit.eval`` and ``fit.ckpt`` where enabled, and
    ``fit.finish``. They go to ``event_log`` (else to the process-wide log,
    if one is configured) and, under a profiler session, onto the
    profile's host plane.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    policy = budget_policy
    if policy is not None:
        _require_history(cfg)  # eager: fail before any compile work
        policy = resolve_horizon(policy, cfg.num_steps)
    with span(scopes.FIT_INIT, log=event_log):
        state = init_outer_state(key, cfg, x, init_params=init_params)
        start_step = 0
        if ckpt_dir and resume and latest_step(ckpt_dir) is not None:
            state, start_step = restore_checkpoint(ckpt_dir, state)

    history = _empty_history()
    t0 = time.perf_counter()

    step = start_step
    while step < cfg.num_steps:
        k = _round_size(step, cfg.num_steps, steps_per_round,
                        eval_every if x_test is not None else 0,
                        ckpt_every if ckpt_dir else 0)
        with span(scopes.FIT_CHUNK, log=event_log, steps=k):
            ts = time.perf_counter()
            if policy is None:
                state, metrics = outer_scan(state, x, y, cfg, k,
                                            numerics=numerics)
            else:
                # The policy rides the scan carry WITHIN a chunk and is
                # handed back in explicitly ACROSS chunks — EMAs, anneal
                # counter and epoch pool are invariant to the chunking.
                (state, policy), metrics = outer_scan(
                    state, x, y, cfg, k, numerics=numerics, budget=policy
                )
            jax.block_until_ready(state.carry_v)
            dt = time.perf_counter() - ts
        with span(scopes.FIT_METRICS, log=event_log):
            _append_round(history, metrics, dt, k, event_log=event_log,
                          solver=cfg.solver.name)
        step += k

        if eval_every and x_test is not None and step % eval_every == 0:
            with span(scopes.FIT_EVAL, log=event_log):
                m = evaluate(x, state, cfg, x_test, y_test,
                             numerics=numerics)
            history["eval_step"].append(step)
            history["eval_rmse"].append(m["rmse"])
            history["eval_llh"].append(m["llh"])
            if verbose:
                print(f"[fit] step {step}: rmse={m['rmse']:.4f} llh={m['llh']:.4f}")

        if ckpt_dir and ckpt_every and step % ckpt_every == 0:
            with span(scopes.FIT_CKPT, log=event_log):
                save_checkpoint(ckpt_dir, step, state)

        if verbose:
            print(
                f"[fit] step {step}/{cfg.num_steps} "
                f"res_y={history['res_y'][-1]:.4f} res_z={history['res_z'][-1]:.4f} "
                f"iters={history['iters'][-1]} ({dt:.2f}s/{k} steps)"
            )

    with span(scopes.FIT_FINISH, log=event_log):
        if ckpt_dir:
            save_checkpoint(ckpt_dir, cfg.num_steps, state)
        wall = time.perf_counter() - t0
        hist = {k_: np.asarray(v) for k_, v in history.items()}
        if event_log is not None:
            event_log.emit(
                "fit_done", solver=cfg.solver.name, num_steps=cfg.num_steps,
                total_iters=int(np.sum(hist["iters"])),
                total_epochs=float(np.sum(hist["epochs"])),
                wall_time_s=wall,
            )
    return FitResult(state=state, history=hist, wall_time_s=wall)


def fit_batch(
    x: jax.Array,
    y: jax.Array,
    cfg: OuterConfig,
    keys: jax.Array,
    init_params: Optional[HyperParams] = None,
    x_test: Optional[jax.Array] = None,
    y_test: Optional[jax.Array] = None,
    verbose: bool = False,
    steps_per_round: int = 0,
    numerics: Optional[SolverNumerics] = None,
    mesh=None,
    event_log=None,
    budget_policy: Optional[BudgetPolicy] = None,
) -> list[FitResult]:
    """Fit B scenario lanes sharing one dataset and static config in ONE
    compiled program (one executable, vmap over lanes, scan over steps).

    Lanes differ in seed (``keys``: (B, 2) or a list of PRNG keys),
    optionally in initial hyperparameters (``init_params`` lane-stacked),
    and optionally in NUMERIC solver settings (``numerics`` lane-stacked:
    per-lane tolerance/budget/lr ride as traced values, so a solver-config
    grid is lanes of this one program too). Everything static — kernel
    kind, solver name, shapes — is shared, which is exactly the
    one-executable-per-group contract ``launch.batch`` partitions sweeps
    by. Lane ``l`` advances as ``fit(x, y, cfg, key=keys[l], ...)`` would
    (solver freeze masks), so results are per-cell comparable with single
    fits.

    ``mesh`` (a 1-D lane mesh, see ``repro.launch.mesh.make_lane_mesh``)
    shards the lane axis across devices: lane-stacked state/numerics are
    placed with ``NamedSharding`` over the mesh's axis, the dataset is
    replicated, and the SAME ``outer_scan`` program runs data-parallel over
    lanes (B must be a multiple of the device count). Per-lane results are
    unchanged up to fp32 accumulation order.

    ``steps_per_round <= 0`` (default) scans all steps in one dispatch.
    Checkpointing is not supported here; per-lane eval runs once at the end
    when ``x_test`` is given. Returned per-lane ``wall_time_s`` is the
    shared wall clock divided by B (the amortised per-scenario cost).
    ``event_log`` emits lane-tagged ``solve_step`` events and the same
    ``fit.*`` spans as :func:`fit`, one per round for all lanes.

    ``budget_policy`` turns on per-lane adaptive budgets: scalar leaves are
    broadcast to every lane, already-(B,)-stacked leaves give each lane its
    own pool/floor/ceiling — the controller then allocates, calibrates and
    anneals independently per lane inside the same executable (lane ``l``
    matches ``fit(..., budget_policy=<lane l's policy>)``). Requires
    ``cfg.solver.record_history >= 2``; see :func:`fit`.
    """
    keys = jnp.asarray(keys)
    lanes = keys.shape[0]
    with span(scopes.FIT_INIT, log=event_log):
        states = init_outer_state_lanes(keys, cfg, x,
                                        init_params=init_params)
        assert num_lanes(states) == lanes
        if numerics is not None:
            numerics = broadcast_numerics(numerics, lanes)
        policy = budget_policy
        if policy is not None:
            _require_history(cfg)  # eager: fail before any compile work
            policy = broadcast_policy(
                resolve_horizon(policy, cfg.num_steps), lanes)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            ndev = mesh.devices.size
            if lanes % ndev != 0:
                raise ValueError(
                    f"lanes={lanes} must be a multiple of the lane-mesh "
                    f"device count {ndev} (pad the grid or drop "
                    f"--shard-lanes)"
                )
            lane_sharding = NamedSharding(mesh,
                                          PartitionSpec(mesh.axis_names[0]))
            replicated = NamedSharding(mesh, PartitionSpec())
            states = jax.device_put(states, lane_sharding)
            x = jax.device_put(x, replicated)
            y = jax.device_put(y, replicated)
            if numerics is not None:
                numerics = jax.device_put(numerics, lane_sharding)
            if policy is not None:
                policy = jax.device_put(policy, lane_sharding)

    histories = [_empty_history() for _ in range(lanes)]
    t0 = time.perf_counter()

    step = 0
    while step < cfg.num_steps:
        k = _round_size(step, cfg.num_steps, steps_per_round)
        with span(scopes.FIT_CHUNK, log=event_log, steps=k):
            ts = time.perf_counter()
            if policy is None:
                states, metrics = outer_scan(states, x, y, cfg, k,
                                             lanes=True, numerics=numerics)
            else:
                (states, policy), metrics = outer_scan(
                    states, x, y, cfg, k, lanes=True, numerics=numerics,
                    budget=policy,
                )
            jax.block_until_ready(states.carry_v)
            dt = time.perf_counter() - ts
        with span(scopes.FIT_METRICS, log=event_log):
            # One device->host transfer per metric, not one per metric per
            # lane.
            metrics = {name: np.asarray(v) for name, v in metrics.items()}
            for lane in range(lanes):
                _append_round(histories[lane], metrics, dt / lanes, k,
                              lane=lane, event_log=event_log,
                              solver=cfg.solver.name)
        step += k
        if verbose:
            print(f"[fit_batch] step {step}/{cfg.num_steps} x {lanes} lanes "
                  f"({dt:.2f}s/{k} steps)")

    wall = time.perf_counter() - t0
    lane_states = [unstack_state(states, lane) for lane in range(lanes)]
    if x_test is not None:
        with span(scopes.FIT_EVAL, log=event_log):
            for lane, lane_state in enumerate(lane_states):
                lane_num = (None if numerics is None
                            else jax.tree.map(lambda v: v[lane], numerics))
                m = evaluate(x, lane_state, cfg, x_test, y_test,
                             numerics=lane_num)
                histories[lane]["eval_step"].append(cfg.num_steps)
                histories[lane]["eval_rmse"].append(m["rmse"])
                histories[lane]["eval_llh"].append(m["llh"])
    with span(scopes.FIT_FINISH, log=event_log):
        return [
            FitResult(state=lane_state,
                      history={k_: np.asarray(v) for k_, v in hist.items()},
                      wall_time_s=wall / lanes)
            for lane_state, hist in zip(lane_states, histories)
        ]


def evaluate(
    x: jax.Array,
    state: OuterState,
    cfg: OuterConfig,
    x_test: jax.Array,
    y_test: jax.Array,
    numerics: Optional[SolverNumerics] = None,
) -> dict:
    """Test RMSE / mean predictive LLH.

    Pathwise estimator: zero extra solves (eq. 16 amortisation) — uses the
    current carry. Standard estimator: runs the s pathwise eval solves the
    paper charges to the standard path (Fig. 1), warm-started from zero.
    """
    kind = effective_kind(cfg, state.params)
    if cfg.estimator == PATHWISE:
        pred = pathwise_predict(
            x, x_test, state.carry_v, state.probes, state.params,
            kind=kind, bm=cfg.bm, bn=cfg.bn,
        )
        m = predictive_metrics(y_test, pred, state.params)
    else:
        n, d = x.shape
        key = jax.random.fold_in(state.key, 7)
        eval_probes = init_probes(
            key, PATHWISE, n, d, state.carry_v.shape[1] - 1,
            cfg.num_rff_pairs, kind=kind, dtype=x.dtype,
        )
        # Reuse v_y from the carry; solve only the s probe systems.
        targets = build_system_targets(eval_probes, x, jnp.zeros((n,), x.dtype),
                                       state.params)
        op = HOperator(x=x, params=state.params, kind=kind,
                       backend=cfg.backend, bm=cfg.bm, bn=cfg.bn)
        scfg = (cfg.solver if cfg.solver.kind == kind
                else replace(cfg.solver, kind=kind))
        res = solve(op, targets[:, 1:], None, scfg, key=key, numerics=numerics)
        v = jnp.concatenate([state.carry_v[:, :1], res.v], axis=1)
        pred = pathwise_predict(x, x_test, v, eval_probes, state.params,
                                kind=kind, bm=cfg.bm, bn=cfg.bn)
        m = predictive_metrics(y_test, pred, state.params)
    return {k: float(v) for k, v in m.items()}
