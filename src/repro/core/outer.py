"""The outer marginal-likelihood optimisation loop (paper Fig. 2, §2.1).

Three-level hierarchy:

    outer   Adam ascent on theta (softplus-reparameterised)
    middle  standard | pathwise gradient estimator
    inner   CG | AP | SGD linear-system solver (warm-started or not)

One `outer_step` = build targets -> (maybe) warm-start from carry ->
inner solve (to tolerance and/or epoch budget) -> gradient assembly ->
Adam update -> new carry. The whole step is a single jitted function;
the solver's while-loop runs under `lax.while_loop`.

Lane batching and scan chunking: the step body is vmap-safe over
lane-stacked `OuterState`s (B scenarios differing in seed/inits advance in
one program — `outer_step_lanes`; the solver freeze masks keep early-
converging lanes identical to single runs) and `outer_scan` runs K steps
under one `lax.scan` dispatch, returning stacked metrics instead of one
host round-trip per step. Static configuration (kernel kind, solver name,
shapes) stays per-executable; grids over it are partitioned by
`repro.launch.batch`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.estimators import (
    PATHWISE,
    STANDARD,
    ProbeState,
    build_system_targets,
    init_probes,
)
from repro.core.gradients import mll_grad_estimate
from repro.gp.hyperparams import HyperParams
from repro.obs import scopes
from repro.solvers import (
    HOperator,
    SolverConfig,
    SolverNumerics,
    numerics_of,
    solve,
)
from repro.solvers.adaptive import (
    MIN_RECORD_HISTORY,
    BudgetPolicy,
    budget_allocate,
    budget_observe,
)
from repro.train.adam import AdamConfig, AdamState, adam_init, adam_update


@dataclass(frozen=True)
class OuterConfig:
    """Static configuration of the outer MLL loop (hashable, jit-static).

    Composes the paper's three-level hierarchy: the gradient estimator
    (standard | pathwise), warm starting, and the inner `SolverConfig`,
    around Adam on the marginal likelihood.
    """

    estimator: str = PATHWISE  # standard | pathwise
    warm_start: bool = True
    num_probes: int = 64  # s (paper default)
    num_rff_pairs: int = 1000  # m sin/cos pairs (2m features)
    kind: Optional[str] = None  # registered kernel; None => params.kernel
    solver: SolverConfig = field(default_factory=SolverConfig)
    adam: AdamConfig = field(default_factory=lambda: AdamConfig(learning_rate=0.1))
    num_steps: int = 100
    backend: str = "streamed"  # HOperator backend
    bm: int = 1024
    bn: int = 1024


def effective_kind(cfg: "OuterConfig", params: HyperParams) -> str:
    """Kernel precedence: OuterConfig.kind > SolverConfig.kind > params.kernel."""
    if cfg.kind is not None:
        return cfg.kind
    if cfg.solver.kind is not None:
        return cfg.solver.kind
    return params.kernel


class OuterState(NamedTuple):
    """Everything that evolves across outer steps (a pytree; checkpointable)."""

    params: HyperParams
    adam: AdamState
    probes: ProbeState
    carry_v: jax.Array  # (n, 1+s) previous solutions (warm-start carry)
    key: jax.Array
    step: jax.Array  # int32

    # Rolling diagnostics from the last step.
    last_res_y: jax.Array
    last_res_z: jax.Array
    last_iters: jax.Array
    last_epochs: jax.Array


def init_outer_state(
    key: jax.Array,
    cfg: OuterConfig,
    x: jax.Array,
    init_params: Optional[HyperParams] = None,
) -> OuterState:
    """Fresh `OuterState`: hyperparameters, Adam, probes, zero carry.

    Args:
      key: PRNG key (split for hypers / probes / the evolving state key).
      cfg: outer-loop config (probe counts, estimator, kernel precedence).
      x: (n, d) training inputs (fixes shapes and dtype).
      init_params: starting `HyperParams`; a kernel-matched default when
        None.
    Returns:
      An `OuterState` with (n, 1+s) zero warm-start carry.
    """
    n, d = x.shape
    kp, kprobe, krest = jax.random.split(key, 3)
    if init_params is not None:
        params = init_params
    else:
        params = HyperParams.create(
            d, kernel=cfg.kind or cfg.solver.kind or "matern32"
        )
    probes = init_probes(
        kprobe, cfg.estimator, n, d, cfg.num_probes, cfg.num_rff_pairs,
        kind=effective_kind(cfg, params), dtype=x.dtype,
    )
    carry = jnp.zeros((n, 1 + cfg.num_probes), dtype=x.dtype)
    z = jnp.zeros((), jnp.float32)
    return OuterState(
        params=params,
        adam=adam_init(params),
        probes=probes,
        carry_v=carry,
        key=krest,
        step=jnp.zeros((), jnp.int32),
        last_res_y=z, last_res_z=z,
        last_iters=jnp.zeros((), jnp.int32), last_epochs=z,
    )


# Geometric capacity-growth factor for sequential appends (online serving /
# BO loops): growing the carry to factor^j * base instead of by the exact
# append size keeps the number of DISTINCT system shapes — and therefore the
# number of compiled solver executables — at O(log N) over N appended rows,
# instead of one retrace per round.
GROWTH_FACTOR = 2.0
MIN_CAPACITY = 16


def grow_capacity(
    current: int,
    needed: int,
    factor: float = GROWTH_FACTOR,
    minimum: int = MIN_CAPACITY,
) -> int:
    """Geometric capacity schedule for append-heavy workloads.

    Returns the smallest capacity ``>= needed`` on the geometric ladder
    ``max(current, minimum) * factor^j`` (j >= 0). Repeated calls over N
    one-row appends therefore return O(log N) distinct values — the compile
    count of any shape-specialised consumer (solvers, the serving engine)
    stays logarithmic in the stream length.

    Args:
      current: the present capacity (row count) of the padded arrays.
      needed: the minimum capacity that must be accommodated.
      factor: geometric growth factor (> 1).
      minimum: floor for the first allocation.
    Returns:
      int capacity ``>= max(needed, current)``.
    """
    if factor <= 1.0:
        raise ValueError(f"growth factor must be > 1, got {factor}")
    cap = max(int(current), int(minimum))
    needed = int(needed)
    while cap < needed:
        cap = max(cap + 1, int(math.ceil(cap * factor)))
    return cap


def extend_state(
    state: OuterState, num_new: int, dtype=None
) -> OuterState:
    """Extend the warm-start carry for ``num_new`` appended observations.

    The online-refresh hook (Dong et al., 2025): when new rows (x, y) stream
    in, the old solutions zero-padded on the new rows are the warm start for
    the enlarged system — the accumulated solver progress on the old rows is
    kept (negligible-bias carry, Lin et al., 2024). Base probe randomness for
    the NEW rows is drawn once here and then fixed, preserving the
    warm-start contract of Appendix B:

      * carry_v gains ``num_new`` zero rows,
      * pathwise ``w_eps`` (standard ``z``) gains ``num_new`` fresh N(0,1)
        rows — the RFF base draws are function-space and need no extension.
    """
    if num_new <= 0:
        return state
    dtype = dtype if dtype is not None else state.carry_v.dtype
    key, knew = jax.random.split(state.key)
    s = state.carry_v.shape[1] - 1
    carry = jnp.concatenate(
        [state.carry_v, jnp.zeros((num_new, 1 + s), dtype=dtype)], axis=0
    )
    probes = state.probes
    if probes.estimator == PATHWISE:
        rows = jax.random.normal(knew, (num_new, s), dtype=dtype)
        probes = probes._replace(
            w_eps=jnp.concatenate([probes.w_eps, rows], axis=0)
        )
    else:
        rows = jax.random.normal(knew, (num_new, probes.z.shape[1]), dtype=dtype)
        probes = probes._replace(z=jnp.concatenate([probes.z, rows], axis=0))
    return state._replace(carry_v=carry, probes=probes, key=key)


def _resample_probes(key: jax.Array, probes: ProbeState, x: jax.Array) -> ProbeState:
    """Fresh base randomness with identical shapes (non-warm-start regime)."""
    n, d = x.shape
    if probes.estimator == STANDARD:
        s = probes.z.shape[1]
        return init_probes(key, STANDARD, n, d, s, dtype=x.dtype)
    m = probes.rff.z.shape[0]
    s = probes.rff.w.shape[1]
    return init_probes(
        key, PATHWISE, n, d, s, num_rff_pairs=m, kind=probes.rff.kind, dtype=x.dtype
    )


def _outer_step(
    state: OuterState, x: jax.Array, y: jax.Array, cfg: OuterConfig,
    numerics: Optional[SolverNumerics] = None,
) -> tuple[OuterState, dict]:
    """One outer MLL step: solve -> gradient -> Adam -> carry (unjitted).

    Pure in ``state`` given static ``cfg`` and safe to ``jax.vmap`` over
    lane-stacked states (the solver while-loops carry per-lane freeze
    masks), so the same body serves :func:`outer_step` (jit),
    :func:`outer_step_lanes` (jit-of-vmap) and :func:`outer_scan`
    (jit-of-scan[-of-vmap]).

    ``numerics`` (traced) overrides the numeric solver settings of
    ``cfg.solver`` — per-lane under vmap, so tolerance/budget/lr grids share
    one executable; None reads them from the static config (same maths).
    """
    kind = effective_kind(cfg, state.params)
    key, ksolve, kprobe = jax.random.split(state.key, 3)

    probes = state.probes
    with jax.named_scope(scopes.TARGETS):
        if not cfg.warm_start:
            probes = _resample_probes(kprobe, probes, x)
        targets = build_system_targets(probes, x, y, state.params)
    v0 = state.carry_v if cfg.warm_start else None

    op = HOperator(
        x=x, params=state.params, kind=kind,
        backend=cfg.backend, bm=cfg.bm, bn=cfg.bn,
    )
    # Align the solver config with the resolved kernel so the documented
    # precedence (OuterConfig.kind > SolverConfig.kind) holds; solve()'s
    # conflict check then only fires for hand-built operator/config pairs.
    scfg = cfg.solver if cfg.solver.kind == kind else replace(cfg.solver, kind=kind)
    with jax.named_scope(scopes.SOLVE):
        res = solve(op, targets, v0, scfg, key=ksolve, numerics=numerics)

    with jax.named_scope(scopes.GRAD):
        grads, aux = mll_grad_estimate(
            x, y, state.params, res.v, targets, cfg.estimator,
            kind=kind, bm=cfg.bm, bn=cfg.bn,
        )
    with jax.named_scope(scopes.ADAM):
        new_params, new_adam = adam_update(
            grads, state.adam, state.params, cfg.adam, maximize=True
        )

    new_state = OuterState(
        params=new_params,
        adam=new_adam,
        probes=probes,
        carry_v=res.v,
        key=key,
        step=state.step + 1,
        last_res_y=res.res_y.astype(jnp.float32),
        last_res_z=res.res_z.astype(jnp.float32),
        last_iters=res.iters,
        last_epochs=res.epochs.astype(jnp.float32),
    )
    metrics = {
        "step": state.step,
        "res_y": res.res_y,
        "res_z": res.res_z,
        "iters": res.iters,
        "epochs": res.epochs,
        "data_fit": aux.data_fit,
        "hypers": new_params.flat(),
        "grad_norm": jnp.sqrt(
            sum(jnp.sum(g**2) for g in jax.tree.leaves(grads))
        ),
    }
    # Solver telemetry (SolverConfig.record_history > 0): the per-iteration
    # residual ring rides the metrics dict. Static-config branch, so the
    # default (off) metrics pytree is unchanged.
    if res.res_history is not None:
        metrics["res_history"] = res.res_history
    return new_state, metrics


outer_step = partial(jax.jit, static_argnames=("cfg",))(_outer_step)


def _outer_step_lanes(
    states: OuterState, x: jax.Array, y: jax.Array, cfg: OuterConfig,
    numerics: Optional[SolverNumerics] = None,
) -> tuple[OuterState, dict]:
    if numerics is None:
        return jax.vmap(lambda s: _outer_step(s, x, y, cfg))(states)
    return jax.vmap(
        lambda s, nm: _outer_step(s, x, y, cfg, nm)
    )(states, numerics)


@partial(jax.jit, static_argnames=("cfg",))
def outer_step_lanes(
    states: OuterState, x: jax.Array, y: jax.Array, cfg: OuterConfig,
    numerics: Optional[SolverNumerics] = None,
) -> tuple[OuterState, dict]:
    """One outer MLL step for B lane-stacked scenarios in one program.

    ``states`` is an :class:`OuterState` whose leaves carry a leading lane
    axis (see :func:`stack_states` / :func:`init_outer_state_lanes`); the
    dataset ``(x, y)`` and the static ``cfg`` — kernel kind, solver name,
    shapes — are shared by every lane. ``numerics`` (optional) must be
    lane-stacked with (B,) leaves: lane ``l`` then solves under its OWN
    tolerance/budget/lr, so solver-config grids are lanes of this one
    executable. Returns lane-stacked ``(new_states, metrics)``; each lane
    advances exactly as it would under a plain :func:`outer_step` (solver
    freeze masks keep early-converging lanes honest).
    """
    return _outer_step_lanes(states, x, y, cfg, numerics)


def _require_history(cfg: OuterConfig) -> None:
    """Trace-time guard: adaptive budgets need the solver residual ring.

    The decay estimator fits a slope to ``SolveResult.res_history``;
    without at least :data:`MIN_RECORD_HISTORY` recorded points there is
    no model to calibrate and the controller would silently run its
    fixed-budget fallback forever — an error beats a misprediction.
    """
    if cfg.solver.record_history < MIN_RECORD_HISTORY:
        raise ValueError(
            "adaptive budgets (budget_policy=) require solver residual "
            f"telemetry: set SolverConfig.record_history >= "
            f"{MIN_RECORD_HISTORY} (got {cfg.solver.record_history}); the "
            "decay estimator fits its model to SolveResult.res_history"
        )


def _outer_step_budget(
    state: OuterState, policy: BudgetPolicy, x: jax.Array, y: jax.Array,
    cfg: OuterConfig, numerics: Optional[SolverNumerics] = None,
) -> tuple[OuterState, BudgetPolicy, dict]:
    """One outer step under the adaptive budget controller (unjitted).

    allocate -> solve (the SAME :func:`_outer_step` body, with
    ``max_epochs`` replaced by the controller's traced allocation) ->
    observe (fold the step's residual ring back into the policy state).
    vmap-safe like :func:`_outer_step`: lane-stacked ``policy`` leaves
    give per-lane budgets inside one executable.

    The metrics dict gains the ``budget_*`` telemetry family — the traced
    half of the ``budget_decision`` event the driver emits per step:
    ``budget_alloc`` (epochs granted), ``budget_pred_to_tol`` (predicted
    epochs to reach tolerance; NaN before the first accepted fit),
    ``budget_realised``/``budget_res``/``budget_slope``/``budget_noise``/
    ``budget_perturbation``/``budget_grad_noise``/``budget_pool``/
    ``budget_epochs_per_iter`` from :func:`budget_observe`.
    """
    _require_history(cfg)
    num = numerics if numerics is not None else numerics_of(cfg.solver)
    alloc, pred = budget_allocate(policy, num)
    new_state, metrics = _outer_step(
        state, x, y, cfg, num._replace(max_epochs=alloc)
    )
    new_policy, decision = budget_observe(
        policy, metrics["res_history"], metrics["iters"], metrics["epochs"],
        metrics["res_y"], metrics["res_z"], num.tolerance,
    )
    metrics["budget_alloc"] = alloc
    metrics["budget_pred_to_tol"] = pred
    for name, val in decision.items():
        metrics[f"budget_{name}"] = val
    return new_state, new_policy, metrics


outer_step_budget = partial(jax.jit, static_argnames=("cfg",))(
    _outer_step_budget
)


def _outer_step_budget_lanes(
    states: OuterState, policy: BudgetPolicy, x: jax.Array, y: jax.Array,
    cfg: OuterConfig, numerics: Optional[SolverNumerics] = None,
) -> tuple[OuterState, BudgetPolicy, dict]:
    if numerics is None:
        return jax.vmap(
            lambda s, p: _outer_step_budget(s, p, x, y, cfg)
        )(states, policy)
    return jax.vmap(
        lambda s, p, nm: _outer_step_budget(s, p, x, y, cfg, nm)
    )(states, policy, numerics)


@partial(jax.jit, static_argnames=("cfg",))
def outer_step_budget_lanes(
    states: OuterState, policy: BudgetPolicy, x: jax.Array, y: jax.Array,
    cfg: OuterConfig, numerics: Optional[SolverNumerics] = None,
) -> tuple[OuterState, BudgetPolicy, dict]:
    """Lane-stacked :func:`outer_step_budget`: each lane allocates, solves
    and observes under its OWN :class:`BudgetPolicy` leaves (and optional
    per-lane ``numerics``) — adaptive tolerance/budget grids stay one
    executable, exactly like :func:`outer_step_lanes`.
    """
    return _outer_step_budget_lanes(states, policy, x, y, cfg, numerics)


@partial(jax.jit, static_argnames=("cfg", "num_steps", "lanes"))
def outer_scan(
    state: OuterState,
    x: jax.Array,
    y: jax.Array,
    cfg: OuterConfig,
    num_steps: int,
    lanes: bool = False,
    numerics: Optional[SolverNumerics] = None,
    budget: Optional[BudgetPolicy] = None,
) -> tuple[OuterState, dict]:
    """Run ``num_steps`` outer MLL steps under one ``lax.scan`` dispatch.

    Kills the per-step host round-trip of the Python driver loop: one
    device program advances the whole chunk and returns stacked metrics
    with a leading ``num_steps`` axis (plus a lane axis right after it when
    ``lanes=True`` and ``state`` is lane-stacked). Step semantics are
    identical to iterating :func:`outer_step` — the scan body is the same
    traced function. ``numerics`` is threaded to every step (lane-stacked
    when ``lanes=True``); with lane-sharded inputs (``NamedSharding`` over
    the lane axis) the same program runs data-parallel across devices.

    ``budget`` (a :class:`BudgetPolicy`, lane-stacked when ``lanes=True``)
    switches the scan body to :func:`_outer_step_budget`: the policy state
    rides the scan carry — EMAs and the epoch pool survive chunk
    boundaries because the caller passes the RETURNED policy into the next
    chunk — and the return value becomes ``((state, policy), metrics)``
    with the ``budget_*`` metrics family stacked over steps. ``None``
    (default) is the existing fixed-budget path, bit-identical to before.
    """
    if budget is None:
        step = _outer_step_lanes if lanes else _outer_step

        def body(s, _):
            return step(s, x, y, cfg, numerics)

        return jax.lax.scan(body, state, None, length=num_steps)

    bstep = _outer_step_budget_lanes if lanes else _outer_step_budget

    def bbody(carry, _):
        s, p = carry
        s2, p2, m = bstep(s, p, x, y, cfg, numerics)
        return (s2, p2), m

    return jax.lax.scan(bbody, (state, budget), None, length=num_steps)


def stack_states(states) -> OuterState:
    """Stack single-scenario :class:`OuterState` pytrees into one lane-
    stacked state (lane axis 0). All states must share static structure
    (kernel kind, estimator, shapes) — that is the one-executable contract.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def unstack_state(states: OuterState, lane: int) -> OuterState:
    """Extract lane ``lane`` of a lane-stacked state as a single state."""
    return jax.tree.map(lambda v: v[lane], states)


def num_lanes(states: OuterState) -> int:
    """Lane count of a lane-stacked state."""
    return states.carry_v.shape[0]


def init_outer_state_lanes(
    keys: jax.Array,
    cfg: OuterConfig,
    x: jax.Array,
    init_params: Optional[HyperParams] = None,
) -> OuterState:
    """Initialise B lanes in one shot: ``keys`` is (B, 2); ``init_params``
    may be lane-stacked (per-lane inits) or unstacked (shared init).
    Lane ``l`` is initialised exactly as ``init_outer_state(keys[l], ...)``.
    """
    if init_params is None:
        return jax.vmap(lambda k: init_outer_state(k, cfg, x))(keys)
    p_axis = 0 if jnp.ndim(init_params.raw_signal) > 0 else None
    return jax.vmap(
        lambda k, p: init_outer_state(k, cfg, x, init_params=p),
        in_axes=(0, p_axis),
    )(keys, init_params)


def exact_outer_step(
    params: HyperParams, adam: AdamState, x: jax.Array, y: jax.Array,
    adam_cfg: AdamConfig, kind: Optional[str] = None,
):
    """Reference: one Adam step on the EXACT Cholesky MLL gradient.

    Produces the paper's exact-optimisation trajectories (Figs. 5/8/11-13).
    """
    from repro.gp.exact import exact_mll

    mll, grads = jax.value_and_grad(lambda p: exact_mll(x, y, p, kind=kind))(params)
    new_params, new_adam = adam_update(grads, adam, params, adam_cfg, maximize=True)
    return new_params, new_adam, mll
