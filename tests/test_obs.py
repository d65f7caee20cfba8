"""Observability layer: metrics registry + Prometheus rendering, trace
IDs/event log/spans, solver residual ring buffers (including vmap lane
parity), and end-to-end trace propagation through a 2-replica cluster."""
import io
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gp.hyperparams import HyperParams
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry, NullRegistry
from repro.solvers import (
    HOperator,
    SolverConfig,
    solve,
    solve_lanes,
)
from repro.solvers.base import history_init, history_record, unroll_history


# -- metrics ------------------------------------------------------------------
def test_counter_gauge_histogram_render():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "Requests", labelnames=("path",))
    c.inc(path="/a")
    c.inc(2.0, path="/a")
    c.inc(path="/b")
    g = reg.gauge("depth", "Queue depth")
    g.set(7)
    h = reg.histogram("lat_seconds", "Latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render()
    assert 'req_total{path="/a"} 3' in text
    assert 'req_total{path="/b"} 1' in text
    assert "# TYPE req_total counter" in text
    assert "depth 7" in text
    # Cumulative buckets + +Inf + sum/count.
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


def test_prometheus_label_escaping():
    """Backslash, quote and newline in label values per the 0.0.4 spec."""
    reg = MetricsRegistry()
    c = reg.counter("esc_total", 'help with "quotes"\nand newline',
                    labelnames=("path",))
    c.inc(path='/pre"dict\n\\x')
    text = reg.render()
    assert r'esc_total{path="/pre\"dict\n\\x"} 1' in text
    # HELP escapes backslash and newline (quotes stay raw).
    assert '# HELP esc_total help with "quotes"\\nand newline' in text
    parsed = [l for l in text.splitlines() if not l.startswith("#")]
    assert all("\n" not in l for l in parsed)


def test_registry_idempotent_and_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total", "x") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", labelnames=("k",))


def test_null_registry_is_inert():
    reg = NullRegistry()
    reg.counter("a_total", "a").inc()
    reg.gauge("b", "b").set(1.0)
    reg.histogram("c", "c").observe(0.5)
    assert reg.render() == ""


# -- trace / event log --------------------------------------------------------
def test_sanitize_trace_id():
    assert obs_trace.sanitize_trace_id("abc-123.X_9") == "abc-123.X_9"
    assert obs_trace.sanitize_trace_id("  ok42  ") == "ok42"
    for bad in (None, "", "has space", "semi;colon", "a" * 200,
                "-leadingdash", 'inj"ect\n'):
        assert obs_trace.sanitize_trace_id(bad) is None


def test_event_log_and_span_carry_trace_id():
    buf = io.StringIO()
    log = obs_trace.EventLog(stream=buf)
    with obs_trace.trace_context("t-1") as tid:
        assert tid == "t-1" and obs_trace.current_trace_id() == "t-1"
        log.emit("thing", value=3)
        with pytest.raises(RuntimeError):
            with obs_trace.span("work", log=log, rows=4):
                raise RuntimeError("boom")
    assert obs_trace.current_trace_id() is None
    events = [json.loads(l) for l in buf.getvalue().splitlines()]
    assert [e["kind"] for e in events] == ["thing", "span"]
    assert all(e["trace_id"] == "t-1" for e in events)
    sp = events[1]
    assert sp["span"] == "work" and sp["error"] == "RuntimeError"
    assert sp["dur_ms"] >= 0 and sp["rows"] == 4
    assert log.events_written == 2


def test_module_emit_noop_until_configured(tmp_path):
    obs_trace.configure()  # ensure cleared
    assert obs_trace.emit("ignored") is None
    path = str(tmp_path / "log" / "events-{pid}.jsonl")
    obs_trace.configure(path=path)
    try:
        obs_trace.emit("hello", n=1)
        expanded = path.replace("{pid}", str(os.getpid()))
        (ev,) = [json.loads(l) for l in open(expanded)]
        assert ev["kind"] == "hello" and ev["n"] == 1
    finally:
        obs_trace.configure()
    assert obs_trace.emit("ignored") is None


def test_span_is_a_profiler_host_event_with_its_fields_as_stats():
    """Under a profiler session a span lands on the profile's host plane,
    the clock the device planes share, its scalar fields as stats."""
    from jax.profiler import ProfileData
    from jaxlib import _profiler

    options = _profiler.ProfileOptions()
    options.python_tracer_level = 0
    session = _profiler.ProfilerSession(options)
    buf = io.StringIO()
    with obs_trace.span("fit.chunk", log=obs_trace.EventLog(stream=buf),
                        steps=8, solver="cg", skipped=[1, 2]):
        jax.block_until_ready(jnp.ones(4) * 2)
    profile = ProfileData.from_serialized_xspace(session.stop())
    found = [dict(ev.stats) for plane in profile.planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events if ev.name == "fit.chunk"]
    assert len(found) == 1
    assert found[0]["steps"] == 8 and found[0]["solver"] == "cg"
    assert "skipped" not in found[0]  # only scalars become stats
    (event,) = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert event["span"] == "fit.chunk" and event["skipped"] == [1, 2]


@pytest.mark.parametrize("module", ["repro.obs", "repro.obs.scopes"])
def test_obs_imports_and_spans_without_jax(module):
    """Replicas and tools/ import repro.obs stdlib-only: neither importing
    it nor opening a span loads JAX."""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    code = (f"import sys, {module}\n"
            "from repro.obs import trace\n"
            "with trace.span('fit.chunk', steps=1):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src))
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# -- solver residual rings ----------------------------------------------------
def _toy_system(n=96, d=2, t=3, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    b = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, t))
    params = HyperParams.create(d, lengthscale=1.2, signal=1.0, noise=0.3)
    op = HOperator(x=x, params=params, bm=64, bn=64)
    return x, b, params, op


@pytest.mark.parametrize("name", ["cg", "ap", "sgd"])
def test_ring_buffer_matches_final_residuals(name):
    _, b, _, op = _toy_system()
    cfg = SolverConfig(name=name, max_epochs=8, precond_rank=0,
                       block_size=32, batch_size=32, tolerance=1e-8,
                       record_history=16)
    res = solve(op, b, None, cfg, key=jax.random.PRNGKey(2))
    assert res.res_history is not None and res.res_history.shape == (16, 2)
    iters = int(res.iters)
    assert iters >= 1
    hist = np.asarray(res.res_history)
    # Slot (iters-1) % H holds the residuals after the last iteration —
    # exactly the SolveResult's reported residuals.
    last = hist[(iters - 1) % 16]
    np.testing.assert_allclose(last, [float(res.res_y), float(res.res_z)],
                               rtol=1e-6)
    # Unwritten slots stay NaN. A written slot may hold inf: at this step
    # size the SGD toy solve diverges, and its last residuals are inf.
    written = (~np.isnan(hist[:, 0])).sum()
    assert written == min(iters, 16)

    # Off path: no history, identical solution bits.
    cfg_off = SolverConfig(name=name, max_epochs=8, precond_rank=0,
                           block_size=32, batch_size=32, tolerance=1e-8)
    res_off = solve(op, b, None, cfg_off, key=jax.random.PRNGKey(2))
    assert res_off.res_history is None
    np.testing.assert_array_equal(np.asarray(res.v), np.asarray(res_off.v))


@pytest.mark.parametrize("name", ["cg", "ap", "sgd"])
def test_ring_buffer_vmap_lane_parity(name):
    """Each lane of a vmapped solve records the same residual trajectory as
    its own single-lane solve — the freeze mask must stop a converged
    lane's ring exactly where the single solve stops."""
    lanes = 3
    x, _, _, _ = _toy_system()
    b = jax.random.normal(jax.random.PRNGKey(7), (lanes, 96, 3))
    # Distinct hypers per lane => distinct convergence points.
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls),
        *[HyperParams.create(2, lengthscale=0.8 + 0.4 * i, signal=1.0,
                             noise=0.2 + 0.1 * i) for i in range(lanes)])
    cfg = SolverConfig(name=name, max_epochs=6, precond_rank=0,
                       block_size=32, batch_size=32, tolerance=1e-8,
                       record_history=8)
    keys = jax.random.split(jax.random.PRNGKey(3), lanes)
    lane_res = solve_lanes(x, stacked, b, None, cfg, bm=64, bn=64, keys=keys)
    assert lane_res.res_history.shape == (lanes, 8, 2)
    for i in range(lanes):
        p = jax.tree_util.tree_map(lambda l: l[i], stacked)
        op = HOperator(x=x, params=p, bm=64, bn=64)
        single = solve(op, b[i], None, cfg, key=keys[i])
        assert int(single.iters) == int(lane_res.iters[i])
        got = np.asarray(lane_res.res_history[i])
        want = np.asarray(single.res_history)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)


def test_unroll_history_wraps_and_stacks():
    hist = history_init(SolverConfig(name="cg", record_history=4),
                        dtype=jnp.float32)
    active = jnp.asarray(True)
    for t in range(6):  # 6 writes into 4 slots: wraps, keeps last 4
        hist = history_record(hist, jnp.asarray(t), jnp.asarray(float(10 + t)),
                              jnp.asarray(float(20 + t)), active)
    rolled = unroll_history(np.asarray(hist), 6)
    np.testing.assert_allclose(rolled[:, 0], [12, 13, 14, 15])
    np.testing.assert_allclose(rolled[:, 1], [22, 23, 24, 25])
    # Fewer writes than slots: time order with NaN tail.
    h2 = history_init(SolverConfig(name="cg", record_history=4))
    h2 = history_record(h2, jnp.asarray(0), jnp.asarray(1.0), jnp.asarray(2.0),
                        active)
    r2 = unroll_history(np.asarray(h2), 1)
    assert r2[0, 0] == 1.0 and np.isnan(r2[1:, 0]).all()
    # Lane-stacked rings unroll per lane.
    stacked = np.stack([np.asarray(hist), np.asarray(hist)])
    rs = unroll_history(stacked, 6)
    assert rs.shape == (2, 4, 2)
    np.testing.assert_allclose(rs[1, :, 0], [12, 13, 14, 15])
    # record_history=0 => no ring at all.
    assert history_init(SolverConfig(name="cg")) is None
    assert history_record(None, jnp.asarray(0), jnp.asarray(1.0),
                          jnp.asarray(1.0), active) is None


# -- end-to-end: trace propagation through a 2-replica cluster ---------------
@pytest.mark.slow
def test_trace_propagates_through_two_replica_cluster(tmp_path):
    """One X-Trace-Id, sent by the client, must surface in the serving
    replica's own request log as the SAME id on the request event, the
    admission event, and the engine.submit span — and come back on the
    response header. Each replica writes its own log file."""
    from repro.core import OuterConfig, init_outer_state, outer_step
    from repro.data.synthetic import make_gp_regression
    from repro.serve import export_servable
    from repro.serve.cluster import ReplicaSupervisor, publish_servable

    x, y = make_gp_regression(jax.random.PRNGKey(0), 160, 2, noise=0.2)
    xq = x[128:132]
    x, y = x[:128], y[:128]
    cfg = OuterConfig(
        estimator="pathwise", warm_start=True, num_probes=8, num_rff_pairs=64,
        solver=SolverConfig(name="cg", max_epochs=200, precond_rank=0),
        num_steps=2, bm=64, bn=64,
    )
    state = init_outer_state(jax.random.PRNGKey(1), cfg, x)
    for _ in range(cfg.num_steps):
        state, _ = outer_step(state, x, y, cfg)
    model = export_servable(state, x)

    store = str(tmp_path / "store")
    log_dir = str(tmp_path / "logs")
    publish_servable(store, model)
    sup = ReplicaSupervisor(store, num_replicas=2, buckets=(8, 32),
                            bm=64, bn=64, poll_interval_s=0.5,
                            request_log_dir=log_dir)
    import urllib.request

    payload = json.dumps({"x": np.asarray(xq).tolist()}).encode()
    try:
        urls = sup.start(timeout_s=240)
        tids = {}
        for i, url in enumerate(urls):
            tid = f"e2e-trace-{i}"
            req = urllib.request.Request(
                url + "/predict", data=payload,
                headers={"Content-Type": "application/json",
                         obs_trace.TRACE_HEADER: tid})
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert resp.status == 200
                assert resp.headers.get(obs_trace.TRACE_HEADER) == tid
            tids[i] = tid

        # Each replica's own log holds its request's full path. emit()
        # flushes per line, so the events are visible while workers run.
        for i in range(2):
            log_path = os.path.join(log_dir, f"replica_{i}.jsonl")
            deadline = time.monotonic() + 30
            by_kind = {}
            while time.monotonic() < deadline:
                events = []
                if os.path.exists(log_path):
                    with open(log_path) as f:
                        for line in f:
                            try:
                                events.append(json.loads(line))
                            except json.JSONDecodeError:
                                pass
                mine = [e for e in events if e.get("trace_id") == tids[i]]
                by_kind = {}
                for e in mine:
                    by_kind.setdefault(e["kind"], []).append(e)
                if {"request", "admission", "span"} <= set(by_kind):
                    break
                time.sleep(0.3)
            assert {"request", "admission", "span"} <= set(by_kind), (
                i, sorted(by_kind))
            req_ev = by_kind["request"][0]
            assert req_ev["path"] == "/predict" and req_ev["status"] == 200
            assert by_kind["admission"][0]["outcome"] == "admitted"
            assert any(e.get("span") == "engine.submit"
                       for e in by_kind["span"])
            # The OTHER replica's trace must not leak into this log.
            other = tids[1 - i]
            assert not [e for e in events if e.get("trace_id") == other]
    finally:
        sup.stop()
