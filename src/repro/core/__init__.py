"""The paper's contribution as a composable system:

estimators (standard | pathwise) x warm starting x compute budgets, around
any registered linear-system solver, driving Adam on the GP marginal
likelihood.
"""
from repro.core.estimators import (
    PATHWISE,
    STANDARD,
    ProbeState,
    build_system_targets,
    expected_initial_sqdistance,
    init_probes,
    probe_targets,
)
from repro.core.gradients import exact_grad_reference, mll_grad_estimate
from repro.core.outer import (
    OuterConfig,
    OuterState,
    effective_kind,
    exact_outer_step,
    extend_state,
    grow_capacity,
    init_outer_state,
    init_outer_state_lanes,
    num_lanes,
    outer_scan,
    outer_step,
    outer_step_lanes,
    stack_states,
    unstack_state,
)
from repro.core.predict import (
    Predictions,
    correction_matrix,
    mean_only_predict,
    pathwise_predict,
    pathwise_predict_from_correction,
    predictive_metrics,
)
from repro.core.driver import (
    SGD_DIVERGENCE_THRESHOLD,
    FitResult,
    evaluate,
    fit,
    fit_batch,
    init_hypers_heuristic,
    pick_sgd_learning_rate,
)

__all__ = [
    "PATHWISE", "STANDARD", "ProbeState", "build_system_targets",
    "expected_initial_sqdistance", "init_probes", "probe_targets",
    "exact_grad_reference", "mll_grad_estimate",
    "OuterConfig", "OuterState", "effective_kind", "exact_outer_step",
    "extend_state", "grow_capacity", "init_outer_state",
    "init_outer_state_lanes",
    "num_lanes", "outer_scan", "outer_step", "outer_step_lanes",
    "stack_states", "unstack_state",
    "Predictions", "correction_matrix", "mean_only_predict",
    "pathwise_predict", "pathwise_predict_from_correction",
    "predictive_metrics",
    "SGD_DIVERGENCE_THRESHOLD",
    "FitResult", "evaluate", "fit", "fit_batch", "init_hypers_heuristic",
    "pick_sgd_learning_rate",
]
