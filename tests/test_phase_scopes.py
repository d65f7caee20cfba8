"""The fit's phases by name: device-side ``gp.*`` scopes in the compiled
outer step, host-side ``fit.*`` spans from the driver (repro.obs.scopes)."""
import contextlib
import io
import json
import re

import jax
import numpy as np
import pytest

from repro.core import OuterConfig, fit, fit_batch, init_outer_state, outer_scan
from repro.data.synthetic import make_gp_regression
from repro.obs import scopes
from repro.obs.trace import EventLog
from repro.solvers import SolverConfig

CFG = OuterConfig(estimator="pathwise", warm_start=True, num_probes=4,
                  num_rff_pairs=32, bm=32, bn=32, num_steps=3,
                  solver=SolverConfig(name="cg", tolerance=0.01,
                                      max_epochs=20, precond_rank=8))
# Opcodes that do the step's arithmetic on matrices: each must be named.
_HEAVY = ("dot", "convolution", "triangular-solve", "custom-call", "cholesky")
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_META = re.compile(r",? metadata=\{[^{}]*\}")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


@pytest.fixture(scope="module")
def problem():
    x, y = make_gp_regression(jax.random.PRNGKey(3), 64, 2, noise=0.3)
    return x, y


def _compiled_step(problem, k=2):
    x, y = problem
    state = init_outer_state(jax.random.PRNGKey(0), CFG, x)
    return outer_scan.lower(state, x, y, CFG, k).compile().as_text()


@pytest.fixture(scope="module")
def heavy_ops(problem):
    """(opcode, scopes on its op_name path) of every matrix operation of
    the compiled outer step, fused or not."""
    out = []
    for line in _compiled_step(problem).splitlines():
        m = _INSTR.match(line)
        if m and m.group(1) in _HEAVY:
            name = _OP_NAME.search(line)
            path = name.group(1) if name else ""
            out.append((m.group(1), set(re.findall(r"gp\.[a-z]+", path))))
    return out


def test_every_matrix_op_of_the_step_carries_a_phase_scope(heavy_ops):
    opcodes = {op for op, _ in heavy_ops}
    assert "dot" in opcodes and opcodes & {"triangular-solve", "custom-call"}
    unnamed = [op for op, found in heavy_ops
               if not found & set(scopes.DEVICE_SCOPES)]
    assert unnamed == []


def test_kernel_mvm_sits_under_the_solve_and_the_gradient_has_its_own(
        heavy_ops):
    mvm = [found for _, found in heavy_ops if scopes.MVM in found]
    grad = [found for _, found in heavy_ops if scopes.GRAD in found]
    assert mvm and grad
    assert all(scopes.SOLVE in found for found in mvm)
    # mll_grad_estimate calls kernel_mvm_tiled itself: no HOperator.mvm.
    assert all(found == {scopes.GRAD} for found in grad)
    precond = [found for _, found in heavy_ops if scopes.PRECOND in found]
    assert precond and all(scopes.SOLVE in found for found in precond)


def test_every_phase_scope_reaches_the_compiled_step(problem):
    text = _compiled_step(problem)
    for name in scopes.DEVICE_SCOPES:
        assert f"/{name}/" in text or f"/{name}\"" in text, name


def _strip(text):
    """The program without its metadata: op names and source tables."""
    lines, skipping = [], False
    for line in text.splitlines():
        if line.strip() in _TABLES:
            skipping = True
            continue
        if skipping and (not line.strip() or re.match(r"^\d+ ", line)):
            continue
        skipping = False
        lines.append(_META.sub("", line))
    return "\n".join(lines)


def test_scopes_change_only_the_metadata_of_the_compiled_step(
        problem, monkeypatch):
    scoped = _compiled_step(problem)
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_step(problem)
    jax.clear_caches()
    assert "gp.solve" in scoped and "gp.solve" not in plain
    assert _strip(scoped) == _strip(plain)


def _spans(buf):
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    return [e for e in events if e["kind"] == "span"], events


def test_fit_emits_its_phase_spans(problem):
    x, y = problem
    buf = io.StringIO()
    res = fit(x, y, CFG, key=jax.random.PRNGKey(1), steps_per_round=2,
              event_log=EventLog(stream=buf))
    spans, events = _spans(buf)
    names = [s["span"] for s in spans]
    assert names == [scopes.FIT_INIT,
                     scopes.FIT_CHUNK, scopes.FIT_METRICS,
                     scopes.FIT_CHUNK, scopes.FIT_METRICS,
                     scopes.FIT_FINISH]
    chunks = [s for s in spans if s["span"] == scopes.FIT_CHUNK]
    assert [c["steps"] for c in chunks] == [2, 1]
    assert sum(c["steps"] for c in chunks) == CFG.num_steps
    assert all(s["dur_ms"] >= 0 for s in spans)
    # The fit's own events still go to the same log, fit_done last.
    assert [e["kind"] for e in events].count("solve_step") == CFG.num_steps
    assert events[-1]["kind"] == "span" and events[-2]["kind"] == "fit_done"
    assert "solver_time_s" not in events[-2]
    assert len(res.history["iters"]) == CFG.num_steps


def test_fit_spans_eval_and_checkpoint_where_enabled(problem, tmp_path):
    x, y = problem
    buf = io.StringIO()
    fit(x, y, CFG, key=jax.random.PRNGKey(1), x_test=x[:8], y_test=y[:8],
        eval_every=2, ckpt_dir=str(tmp_path), ckpt_every=2,
        event_log=EventLog(stream=buf))
    names = [s["span"] for s in _spans(buf)[0]]
    # Chunks stop at the step-2 boundary: [2, 1].
    assert names == [scopes.FIT_INIT,
                     scopes.FIT_CHUNK, scopes.FIT_METRICS,
                     scopes.FIT_EVAL, scopes.FIT_CKPT,
                     scopes.FIT_CHUNK, scopes.FIT_METRICS,
                     scopes.FIT_FINISH]


def test_fit_batch_emits_one_span_per_round_for_all_lanes(problem):
    x, y = problem
    buf = io.StringIO()
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    results = fit_batch(x, y, CFG, keys, x_test=x[:8], y_test=y[:8],
                        steps_per_round=2, event_log=EventLog(stream=buf))
    spans, events = _spans(buf)
    assert [s["span"] for s in spans] == [
        scopes.FIT_INIT,
        scopes.FIT_CHUNK, scopes.FIT_METRICS,
        scopes.FIT_CHUNK, scopes.FIT_METRICS,
        scopes.FIT_EVAL, scopes.FIT_FINISH]
    assert [s["steps"] for s in spans if "steps" in s] == [2, 1]
    lanes = [e["lane"] for e in events if e["kind"] == "solve_step"]
    assert sorted(lanes) == [0] * 3 + [1] * 3
    assert all(len(r.history["eval_rmse"]) == 1 for r in results)
    assert all(np.all(np.isfinite(r.history["res_y"])) for r in results)
