"""Lane-batched scenario engine parity: vmapped solves vs loops of single
solves (freeze masks), scan-chunked fit vs per-step fit (bitwise), lane-
stacked outer steps and fit_batch vs single fits, the named SGD divergence
threshold, and the driver's solver-time accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OuterConfig,
    fit,
    fit_batch,
    init_outer_state,
    init_outer_state_lanes,
    outer_scan,
    outer_step,
    outer_step_lanes,
    unstack_state,
)
from repro.core.driver import (
    SGD_DIVERGENCE_THRESHOLD,
    pick_sgd_learning_rate,
)
from repro.data.synthetic import make_gp_regression
from repro.gp.hyperparams import HyperParams
from repro.solvers import HOperator, SolverConfig, solve, solve_lanes

TOL = 0.01
LANES = 3


@pytest.fixture(scope="module")
def lane_problem():
    """Shared inputs x, per-lane hyperparameters and right-hand sides."""
    n, d, s = 96, 2, 4
    x, y = make_gp_regression(jax.random.PRNGKey(0), n, d, noise=0.3)
    b1 = jnp.concatenate(
        [y[:, None], jax.random.normal(jax.random.PRNGKey(1), (n, s))], axis=1
    )
    params = [
        HyperParams.create(d, lengthscale=0.6 + 0.3 * i, noise=0.3 + 0.25 * i)
        for i in range(LANES)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params)
    b = jnp.stack([b1 * (1.0 + 0.1 * i) for i in range(LANES)])
    keys = jax.random.split(jax.random.PRNGKey(9), LANES)
    return {"x": x, "n": n, "d": d, "params": params, "stacked": stacked,
            "b": b, "keys": keys}


SOLVERS = [
    ("cg", dict(precond_rank=15)),
    ("ap", dict(block_size=32)),
    ("sgd", dict(batch_size=32, learning_rate=2.0)),
]


@pytest.mark.parametrize("name,kw", SOLVERS)
@pytest.mark.parametrize("warm", [False, True])
def test_lane_solve_matches_loop_of_single_solves(lane_problem, name, kw, warm):
    """A vmapped lane-batched solve must reproduce each lane's single-lane
    solve: same per-lane iteration counts (freeze masks keep early finishers
    honest) and the same solutions to fp32 accumulation tolerance."""
    lp = lane_problem
    cfg = SolverConfig(name=name, tolerance=TOL, max_epochs=2000, **kw)
    v0 = (0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                  lp["b"].shape) if warm else None)
    res_b = solve_lanes(lp["x"], lp["stacked"], lp["b"], v0, cfg,
                        bm=64, bn=64, keys=lp["keys"])
    for i in range(LANES):
        op = HOperator(x=lp["x"], params=lp["params"][i], bm=64, bn=64)
        r = solve(op, lp["b"][i], v0[i] if warm else None, cfg,
                  key=lp["keys"][i])
        assert int(res_b.iters[i]) == int(r.iters), (name, warm, i)
        vb, vs = np.asarray(res_b.v[i]), np.asarray(r.v)
        rel = np.linalg.norm(vb - vs) / np.linalg.norm(vs)
        assert rel < 1e-3, (name, warm, i, rel)
        np.testing.assert_allclose(
            float(res_b.res_y[i]), float(r.res_y), rtol=1e-2, atol=1e-4)


@pytest.mark.parametrize("name,kw", [
    ("cg", dict(precond_rank=15)), ("ap", dict(block_size=32)),
])
def test_converged_lane_freezes(lane_problem, name, kw):
    """A lane warm-started at its exact solution is converged at entry: the
    shared while-loop keeps running for the other lane, but the frozen lane
    must report 0 iterations and return its warm start unchanged (up to the
    normalise/denormalise round trip) — the freeze-mask contract."""
    lp = lane_problem
    cfg = SolverConfig(name=name, tolerance=TOL, max_epochs=2000, **kw)
    two = jax.tree.map(lambda v: v[:2], lp["stacked"])
    h0 = (np.asarray(HOperator(x=lp["x"], params=lp["params"][0]).dense()))
    v_exact = jnp.asarray(np.linalg.solve(h0, np.asarray(lp["b"][0])))
    v0 = jnp.stack([v_exact, jnp.zeros_like(v_exact)])
    res = solve_lanes(lp["x"], two, lp["b"][:2], v0, cfg, bm=64, bn=64,
                      keys=lp["keys"][:2])
    assert int(res.iters[0]) == 0
    assert int(res.iters[1]) > 0
    np.testing.assert_allclose(np.asarray(res.v[0]), np.asarray(v_exact),
                               rtol=1e-5, atol=1e-6)
    # the live lane still solved its system
    assert float(res.res_y[1]) <= TOL * 1.01


OUTER_CFG = dict(num_probes=4, num_rff_pairs=64, bm=64, bn=64,
                 solver=SolverConfig(name="cg", tolerance=TOL, max_epochs=50,
                                     precond_rank=0))


@pytest.fixture(scope="module")
def outer_problem():
    x, y = make_gp_regression(jax.random.PRNGKey(2), 64, 2, noise=0.3)
    return x, y


def test_outer_scan_matches_step_loop_bitwise(outer_problem):
    """outer_scan runs the same traced body as outer_step: the trajectory
    must be bitwise identical, for one scan and for chunked scans."""
    x, y = outer_problem
    cfg = OuterConfig(estimator="pathwise", warm_start=True, num_steps=6,
                      **OUTER_CFG)
    st0 = init_outer_state(jax.random.PRNGKey(3), cfg, x)
    st_loop = st0
    hypers = []
    for _ in range(6):
        st_loop, m = outer_step(st_loop, x, y, cfg)
        hypers.append(np.asarray(m["hypers"]))
    st_scan, ms = outer_scan(st0, x, y, cfg, 6)
    np.testing.assert_array_equal(np.stack(hypers), np.asarray(ms["hypers"]))
    np.testing.assert_array_equal(np.asarray(st_loop.carry_v),
                                  np.asarray(st_scan.carry_v))
    # chunking must not change the trajectory either
    sa, _ = outer_scan(st0, x, y, cfg, 3)
    sb, _ = outer_scan(sa, x, y, cfg, 3)
    np.testing.assert_array_equal(np.asarray(st_scan.carry_v),
                                  np.asarray(sb.carry_v))


def test_scan_chunked_fit_matches_per_step_fit_bitwise(outer_problem):
    """fit(steps_per_round=4) histories are bitwise equal to the per-step
    fit(steps_per_round=1) — the scan chunking is pure orchestration."""
    x, y = outer_problem
    cfg = OuterConfig(estimator="pathwise", warm_start=True, num_steps=6,
                      **OUTER_CFG)
    r1 = fit(x, y, cfg, key=jax.random.PRNGKey(5), steps_per_round=1)
    r4 = fit(x, y, cfg, key=jax.random.PRNGKey(5), steps_per_round=4)
    for k in ("res_y", "res_z", "iters", "epochs", "hypers", "grad_norm"):
        np.testing.assert_array_equal(r1.history[k], r4.history[k], err_msg=k)


def test_outer_step_lanes_matches_loop(outer_problem):
    """One lane-stacked outer step == a loop of single outer steps."""
    x, y = outer_problem
    cfg = OuterConfig(estimator="pathwise", warm_start=True, num_steps=2,
                      **OUTER_CFG)
    keys = jax.random.split(jax.random.PRNGKey(11), LANES)
    states = init_outer_state_lanes(keys, cfg, x)
    for _ in range(2):
        states, ml = outer_step_lanes(states, x, y, cfg)
    for i in range(LANES):
        st = init_outer_state(keys[i], cfg, x)
        for _ in range(2):
            st, m = outer_step(st, x, y, cfg)
        assert int(ml["iters"][i]) == int(m["iters"])
        np.testing.assert_allclose(
            np.asarray(unstack_state(states, i).carry_v),
            np.asarray(st.carry_v), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(unstack_state(states, i).params.flat()),
            np.asarray(st.params.flat()), rtol=1e-5, atol=1e-6)


def test_fit_batch_matches_single_fits(outer_problem):
    """fit_batch lanes reproduce per-seed single fits (history parity)."""
    x, y = outer_problem
    cfg = OuterConfig(estimator="pathwise", warm_start=True, num_steps=4,
                      **OUTER_CFG)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    batch = fit_batch(x, y, cfg, keys)
    assert len(batch) == 2
    for i in range(2):
        single = fit(x, y, cfg, key=keys[i])
        np.testing.assert_array_equal(batch[i].history["iters"],
                                      single.history["iters"])
        np.testing.assert_allclose(batch[i].history["hypers"],
                                   single.history["hypers"],
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(batch[i].history["res_y"],
                                   single.history["res_y"],
                                   rtol=1e-2, atol=1e-5)


def test_sgd_divergence_threshold_constant_and_grid_search(outer_problem):
    """The magic `2.0 * 2.0` is now the named, documented constant; the grid
    search keeps the largest stable lr and rejects a diverging one."""
    assert SGD_DIVERGENCE_THRESHOLD == 4.0
    x, y = outer_problem
    cfg = OuterConfig(estimator="pathwise", warm_start=True, num_steps=1,
                      num_probes=4, num_rff_pairs=64, bm=64, bn=64,
                      solver=SolverConfig(name="sgd", tolerance=TOL,
                                          batch_size=32, max_epochs=3))
    key = jax.random.PRNGKey(0)
    params = HyperParams.create(2, noise=0.5)
    # 1e6 blows past the quadratic's stability limit -> rejected.
    lr = pick_sgd_learning_rate(x, y, params, cfg, key, grid=[0.5, 1e6])
    assert lr == 0.5
    # An infinite threshold accepts any finite residual -> largest grid lr
    # (grid order must not matter; the search sorts ascending).
    lr_inf = pick_sgd_learning_rate(x, y, params, cfg, key, grid=[1.0, 0.5],
                                    divergence_threshold=float("inf"))
    assert lr_inf == 1.0
    assert pick_sgd_learning_rate(x, y, params, cfg, key, grid=[1.0, 0.5],
                                  divergence_threshold=float("inf"),
                                  halve=True) == 0.5


def test_launch_batch_one_executable_per_group(tmp_path):
    """launch.batch end-to-end (in-process): a 2-kernel x 2-seed grid runs
    as 2 groups with exactly one compile each, emits one JSON per cell plus
    a sweep status, and skips completed cells on re-run."""
    import json

    from repro.launch import batch

    out = str(tmp_path / "batch")
    argv = ["--out", out, "--dataset", "pol", "--max-n", "128",
            "--kernels", "rbf,matern52", "--seeds", "2", "--steps", "2",
            "--smoke", "--bm", "64", "--bn", "64",
            "--expect-one-compile-per-group"]
    assert batch.main(argv) == 0
    cells = sorted(p.name for p in (tmp_path / "batch").iterdir()
                   if not p.name.startswith("_"))
    assert cells == [
        "gp-iterative-matern52__s0.json", "gp-iterative-matern52__s1.json",
        "gp-iterative-rbf__s0.json", "gp-iterative-rbf__s1.json",
    ]
    with open(tmp_path / "batch" / "_sweep_status.json") as f:
        status = json.load(f)
    assert status["groups"] == 2 and status["num_compiles"] == 2
    assert status["cells"] == 4 and not status["failures"]
    rec = json.loads((tmp_path / "batch" / cells[0]).read_text())
    assert rec["kernel"] == "matern52" and rec["mode"] == "batched"
    assert len(rec["history"]["res_y"]) == 2
    # resumability: everything done -> nothing re-runs, still a success
    assert batch.main(argv[: -1]) == 0
    with open(tmp_path / "batch" / "_sweep_status.json") as f:
        assert json.load(f)["cells"] == 0
