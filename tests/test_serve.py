"""Serving subsystem: bucket padding, artifact round-trip, zero-retrace
steady state, microbatch coalescing, online refresh, multi-model routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OuterConfig,
    correction_matrix,
    extend_state,
    init_outer_state,
    outer_step,
    pathwise_predict,
    pathwise_predict_from_correction,
)
from repro.data.synthetic import make_gp_regression
from repro.serve import (
    BucketedEngine,
    MultiModelServer,
    OnlineGP,
    export_servable,
    load_servable,
    save_servable,
    servable_predict,
)
from repro.solvers import SolverConfig


@pytest.fixture(scope="module")
def fitted():
    """A small pathwise fit (converged CG) plus its data."""
    x, y = make_gp_regression(jax.random.PRNGKey(0), 160, 2, noise=0.2)
    xq = x[128:]
    x, y = x[:128], y[:128]
    cfg = OuterConfig(
        estimator="pathwise", warm_start=True, num_probes=8, num_rff_pairs=64,
        solver=SolverConfig(name="cg", max_epochs=200, precond_rank=0),
        num_steps=3, bm=64, bn=64,
    )
    state = init_outer_state(jax.random.PRNGKey(1), cfg, x)
    for _ in range(cfg.num_steps):
        state, _ = outer_step(state, x, y, cfg)
    return {"x": x, "y": y, "xq": xq, "cfg": cfg, "state": state}


@pytest.fixture(scope="module")
def model(fitted):
    return export_servable(fitted["state"], fitted["x"])


def test_export_matches_pathwise_predict(fitted, model):
    st = fitted["state"]
    want = pathwise_predict(fitted["x"], fitted["xq"], st.carry_v, st.probes,
                            st.params, bm=64, bn=64)
    got = servable_predict(model, fitted["xq"], bm=64, bn=64)
    np.testing.assert_allclose(np.asarray(got.mean), np.asarray(want.mean),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.var), np.asarray(want.var),
                               rtol=1e-5, atol=1e-6)


def test_bucket_padding_agrees_with_unpadded(fitted, model):
    """Padded-to-bucket predictions equal the direct unpadded ones row-wise."""
    engine = BucketedEngine(model, buckets=(8, 32), bm=64, bn=64)
    xq = fitted["xq"][:13]  # ragged: padded to the 32 bucket
    got = engine.submit(xq)
    want = servable_predict(model, xq, bm=64, bn=64)
    assert got.mean.shape == (13,)
    np.testing.assert_allclose(np.asarray(got.mean), np.asarray(want.mean),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.var), np.asarray(want.var),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.samples),
                               np.asarray(want.samples), rtol=1e-5, atol=1e-6)


def test_engine_zero_retrace_after_warmup(fitted, model):
    engine = BucketedEngine(model, buckets=(8, 32), bm=64, bn=64)
    compiles = engine.warmup()
    assert compiles == 2  # one executable per bucket
    for m in (1, 3, 8, 9, 20, 32, 5):
        pred = engine.submit(fitted["xq"][:m])
        assert pred.mean.shape == (m,)
    assert engine.num_compiles() == compiles  # zero retraces in steady state
    assert engine.stats.requests == 7


def test_engine_chunks_oversized_queries(fitted, model):
    engine = BucketedEngine(model, buckets=(8,), bm=64, bn=64)
    xq = fitted["xq"][:20]  # 3 chunks of <= 8
    got = engine.submit(xq)
    want = servable_predict(model, xq, bm=64, bn=64)
    assert got.mean.shape == (20,)
    np.testing.assert_allclose(np.asarray(got.mean), np.asarray(want.mean),
                               rtol=1e-5, atol=1e-6)


def test_engine_queue_microbatches(fitted, model):
    engine = BucketedEngine(model, buckets=(8, 32), bm=64, bn=64)
    engine.warmup()
    try:
        futs = [engine.enqueue(fitted["xq"][i : i + 4]) for i in range(6)]
        for i, f in enumerate(futs):
            pred = f.result(timeout=30)
            want = servable_predict(model, fitted["xq"][i : i + 4],
                                    bm=64, bn=64)
            np.testing.assert_allclose(np.asarray(pred.mean),
                                       np.asarray(want.mean),
                                       rtol=1e-5, atol=1e-6)
    finally:
        engine.stop()
    assert engine.stats.requests == 6
    assert engine.stats.batches <= 6  # some coalescing or at worst 1:1


def test_artifact_save_load_roundtrip(tmp_path, fitted, model):
    save_servable(str(tmp_path), model, step=4)
    loaded = load_servable(str(tmp_path))
    assert loaded.kind == model.kind
    assert loaded.rff.kind == model.rff.kind
    assert loaded.params.kernel == model.params.kernel
    for a, b in zip(jax.tree.leaves(model), jax.tree.leaves(loaded)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    want = servable_predict(model, fitted["xq"], bm=64, bn=64)
    got = servable_predict(loaded, fitted["xq"], bm=64, bn=64)
    np.testing.assert_allclose(np.asarray(got.mean), np.asarray(want.mean),
                               rtol=1e-6)


def test_export_requires_pathwise(fitted):
    cfg = OuterConfig(estimator="standard", num_probes=4,
                      solver=SolverConfig(precond_rank=0))
    st = init_outer_state(jax.random.PRNGKey(2), cfg, fitted["x"])
    with pytest.raises(ValueError, match="pathwise"):
        export_servable(st, fitted["x"])


def test_extend_state_shapes_and_carry(fitted):
    st = fitted["state"]
    n, s1 = st.carry_v.shape
    ext = extend_state(st, 16)
    assert ext.carry_v.shape == (n + 16, s1)
    np.testing.assert_allclose(np.asarray(ext.carry_v[:n]),
                               np.asarray(st.carry_v))
    assert np.all(np.asarray(ext.carry_v[n:]) == 0.0)
    assert ext.probes.w_eps.shape == (n + 16, s1 - 1)
    np.testing.assert_allclose(np.asarray(ext.probes.w_eps[:n]),
                               np.asarray(st.probes.w_eps))
    # fresh base noise on the new rows, not zeros
    assert float(jnp.std(ext.probes.w_eps[n:])) > 0.1
    assert extend_state(st, 0) is st


def test_refresh_then_swap_preserves_old_predictions(fitted, model):
    """Appending data + warm refine must not distort predictions on old
    points beyond solver tolerance; the swap is atomic on the engine."""
    engine = BucketedEngine(model, buckets=(32,), bm=64, bn=64)
    before = engine.submit(fitted["xq"])
    # New observations of the SAME latent function as the fixture (same
    # key, so the same RFF prior draw), at fresh inputs. A different key
    # draws a different function, whose points may rightly move old-point
    # predictions by several std.
    x_all, y_all = make_gp_regression(jax.random.PRNGKey(0), 160 + 24, 2,
                                      noise=0.2)
    x_new, y_new = x_all[160:], y_all[160:]
    online = OnlineGP(fitted["x"], fitted["y"], fitted["state"], fitted["cfg"])
    online.append(x_new, y_new)
    report = online.refresh_into(engine, budget_epochs=200.0)
    assert report.n == 128 + 24
    assert report.res_y <= 2 * fitted["cfg"].solver.tolerance
    after = engine.submit(fitted["xq"])
    assert engine.model.n == 128 + 24  # swap happened
    scale = float(jnp.std(before.mean)) + 1e-6
    diff = float(jnp.max(jnp.abs(after.mean - before.mean))) / scale
    assert diff < 0.5, f"old-point predictions moved {diff:.2f}x std"


def test_merge_preserves_rows_appended_during_refine(fitted):
    """An append that races a background refine must survive the commit:
    the solved rows overwrite only the snapshot prefix."""
    from repro.serve import merge_refined_state

    st = fitted["state"]
    n = st.carry_v.shape[0]
    snapshot = st
    current = extend_state(st, 8)  # append happened while refine was solving
    refined = snapshot._replace(carry_v=snapshot.carry_v + 1.0)
    merged = merge_refined_state(current, refined)
    assert merged.carry_v.shape[0] == n + 8
    np.testing.assert_allclose(np.asarray(merged.carry_v[:n]),
                               np.asarray(refined.carry_v))
    assert np.all(np.asarray(merged.carry_v[n:]) == 0.0)  # extension kept
    assert merged.probes.w_eps.shape[0] == n + 8  # extended probes kept


def test_refresh_into_background_returns_future(fitted, model):
    engine = BucketedEngine(model, buckets=(32,), bm=64, bn=64)
    online = OnlineGP(fitted["x"], fitted["y"], fitted["state"], fitted["cfg"])
    x_new, y_new = make_gp_regression(jax.random.PRNGKey(21), 8, 2, noise=0.2)
    online.append(x_new, y_new)
    fut = online.refresh_into(engine, budget_epochs=50.0, background=True)
    report = fut.result(timeout=120)
    assert report.n == 128 + 8
    assert engine.model.n == 128 + 8  # swap landed
    # failures must surface through the future, not die with the thread
    bad = OnlineGP(fitted["x"], fitted["y"], fitted["state"], fitted["cfg"])
    fut = bad.refresh_into(engine, mode="nope", background=True)
    with pytest.raises(ValueError, match="unknown refine mode"):
        fut.result(timeout=120)


def test_warm_refresh_cheaper_than_cold(fitted):
    x_new, y_new = make_gp_regression(jax.random.PRNGKey(11), 32, 2, noise=0.2)
    epochs = {}
    for warm in (True, False):
        online = OnlineGP(fitted["x"], fitted["y"], fitted["state"],
                          fitted["cfg"])
        online.append(x_new, y_new)
        epochs[warm] = online.refine(warm=warm, mode="solve").epochs
    assert epochs[True] < epochs[False], epochs


def test_multimodel_registry_routes_and_swaps(fitted):
    st, x = fitted["state"], fitted["x"]
    m32 = export_servable(st, x)
    rbf_params = st.params._replace(kernel="rbf")
    mrbf = export_servable(st._replace(params=rbf_params), x, kind="rbf")
    server = MultiModelServer(buckets=(8, 32), bm=64, bn=64)
    server.register("m32", m32)
    server.register("rbf", mrbf)
    assert server.names() == ("m32", "rbf")
    compiles = server.warmup()
    assert compiles == 4  # 2 buckets x 2 kernels, one shared jit cache
    p32 = server.submit("m32", fitted["xq"][:8])
    prbf = server.submit("rbf", fitted["xq"][:8])
    # different kernels must route to different executables/results
    assert float(jnp.max(jnp.abs(p32.mean - prbf.mean))) > 1e-6
    assert server.engine.num_compiles() == compiles
    server.swap("m32", mrbf)
    np.testing.assert_allclose(
        np.asarray(server.submit("m32", fitted["xq"][:8]).mean),
        np.asarray(prbf.mean), rtol=1e-6,
    )
    with pytest.raises(ValueError, match="already registered"):
        server.register("m32", m32)
    with pytest.raises(KeyError):
        server.submit("nope", fitted["xq"][:8])


@pytest.fixture(scope="module")
def block_fit():
    """Tight-tolerance fit whose carry is synced to the final
    hyperparameters (an outer step leaves the carry one Adam update
    behind; the sync isolates the block-vs-full comparison)."""
    xall, yall = make_gp_regression(jax.random.PRNGKey(0), 208, 2, noise=0.2)
    x, y = xall[:128], yall[:128]
    cfg = OuterConfig(
        estimator="pathwise", warm_start=True, num_probes=8, num_rff_pairs=64,
        solver=SolverConfig(name="cg", max_epochs=400, precond_rank=0,
                            tolerance=1e-5),
        num_steps=3, bm=64, bn=64,
    )
    state = init_outer_state(jax.random.PRNGKey(1), cfg, x)
    for _ in range(cfg.num_steps):
        state, _ = outer_step(state, x, y, cfg)
    sync = OnlineGP(x, y, state, cfg)
    sync.refine(mode="solve")
    return {"x": x, "y": y, "xq": xall[144:], "cfg": cfg,
            "state": sync.state, "overlap": (xall[128:144], yall[128:144])}


def test_block_refresh_matches_full_resolve_weak_coupling(block_fit):
    """Acceptance: block refine matches the full re-solve within tolerance
    while its solver only runs on the new-row block (epoch accounting).

    Weak coupling (an appended cluster ~10 lengthscales away) is the block
    mode's validity regime: there the neglected back-coupling K12 dv is
    ~zero and the parity is at solver-tolerance level."""
    k = 16
    x_new = block_fit["x"][:k] + 8.0
    y_new = jax.random.normal(jax.random.PRNGKey(3), (k,)) * 0.5
    online = {}
    for mode in ("block", "solve"):
        o = OnlineGP(block_fit["x"], block_fit["y"], block_fit["state"],
                     block_fit["cfg"])
        o.append(x_new, y_new)
        online[mode] = (o, o.refine(mode=mode))
    rb, rf = online["block"][1], online["solve"][1]
    assert rb.mode == "block" and rb.block_rows == k
    # epoch accounting: the block path pays 2k/n cross-MVM epochs plus the
    # k-system solve scaled by (k/n)^2 — a tiny fraction of the full solve.
    assert rb.epochs < 0.1 * rf.epochs, (rb.epochs, rf.epochs)
    assert rb.block_epochs > 0  # the k x k solver actually ran
    # the neglected-coupling residual is at solver-tolerance scale here
    assert rb.res_y < 1e-3, rb.res_y
    # parity on predictions, old region and new region
    for xq in (block_fit["xq"], x_new + 0.1):
        pb = servable_predict(export_servable(online["block"][0].state,
                                              online["block"][0].x),
                              xq, bm=64, bn=64)
        pf = servable_predict(export_servable(online["solve"][0].state,
                                              online["solve"][0].x),
                              xq, bm=64, bn=64)
        scale = float(jnp.std(pf.mean)) + 1e-6
        assert float(jnp.max(jnp.abs(pb.mean - pf.mean))) / scale < 0.01
        assert float(jnp.max(jnp.abs(pb.var - pf.var))) < 0.01


def test_block_refresh_coupling_residual_flags_overlap(block_fit):
    """Strongly coupled appends (same region as the bulk) are OUTSIDE the
    block mode's validity regime; the reported residual must say so loudly
    instead of pretending the system is solved."""
    x_new, y_new = block_fit["overlap"]
    o = OnlineGP(block_fit["x"], block_fit["y"], block_fit["state"],
                 block_fit["cfg"])
    o.append(x_new, y_new)
    report = o.refine(mode="block")
    assert report.res_y > 0.01, (
        f"overlapping appends must surface a large coupling residual, "
        f"got {report.res_y}"
    )


def test_auto_refresh_stays_block_under_weak_coupling(block_fit):
    """ROADMAP follow-up (b): mode="auto" triggers block-vs-full off the
    reported coupling residual. Weakly coupled appends (a far-away cluster)
    leave the residual at ~tolerance scale, so auto must keep the cheap
    block path: no escalation, block-refresh epoch accounting."""
    k = 16
    x_new = block_fit["x"][:k] + 8.0
    y_new = jax.random.normal(jax.random.PRNGKey(3), (k,)) * 0.5
    o = OnlineGP(block_fit["x"], block_fit["y"], block_fit["state"],
                 block_fit["cfg"])
    o.append(x_new, y_new)
    report = o.refine(mode="auto")
    assert report.mode == "auto" and not report.escalated
    assert report.block_rows == k and report.block_epochs > 0
    # still the incremental price: a tiny fraction of a full epoch
    assert report.epochs < 1.0, report.epochs
    tol = block_fit["cfg"].solver.tolerance
    assert max(report.res_y, report.res_z) <= 5.0 * tol


def test_auto_refresh_escalates_under_strong_coupling(block_fit):
    """Strongly coupled appends (same region as the bulk) push the coupling
    residual orders of magnitude past tolerance: auto must pay the full
    re-solve — warm from the block-corrected carry — and report both the
    escalation and a residual back at solver tolerance, instead of
    silently returning a large res_y as plain mode="block" does."""
    x_new, y_new = block_fit["overlap"]
    blocked = OnlineGP(block_fit["x"], block_fit["y"], block_fit["state"],
                       block_fit["cfg"])
    blocked.append(x_new, y_new)
    block_report = blocked.refine(mode="block")  # the silent-residual path

    o = OnlineGP(block_fit["x"], block_fit["y"], block_fit["state"],
                 block_fit["cfg"])
    o.append(x_new, y_new)
    report = o.refine(mode="auto")
    tol = block_fit["cfg"].solver.tolerance
    assert report.mode == "auto" and report.escalated
    assert block_report.res_y > 5.0 * tol  # block alone left it unsolved
    assert max(report.res_y, report.res_z) <= tol * 1.01  # auto solved it
    # escalation charges block attempt + full solve: more than either alone
    assert report.epochs > block_report.epochs
    assert report.block_rows == x_new.shape[0]
    # an explicit lax threshold keeps the block path instead
    o2 = OnlineGP(block_fit["x"], block_fit["y"], block_fit["state"],
                  block_fit["cfg"])
    o2.append(x_new, y_new)
    lax_report = o2.refine(mode="auto", coupling_threshold=10.0)
    assert not lax_report.escalated
    assert lax_report.epochs < report.epochs


def test_block_refresh_requires_warm_and_noop_without_appends(block_fit):
    o = OnlineGP(block_fit["x"], block_fit["y"], block_fit["state"],
                 block_fit["cfg"])
    with pytest.raises(ValueError, match="warm"):
        o.refine(mode="block", warm=False)
    report = o.refine(mode="block")  # nothing appended => no-op
    assert report.appended == 0 and report.epochs == 0.0
    np.testing.assert_allclose(np.asarray(o.state.carry_v),
                               np.asarray(block_fit["state"].carry_v))


def test_single_sample_variance_raises(fitted):
    """Regression: s=1 used to silently return a zero-information variance
    through jnp.maximum(s - 1, 1); it must fail loudly now."""
    st = fitted["state"]
    corr = correction_matrix(st.carry_v[:, :2])  # keep only [v_y | z_1]
    rff1 = st.probes.rff._replace(w=st.probes.rff.w[:, :1])  # 1 prior sample
    with pytest.raises(ValueError, match=">= 2 pathwise samples"):
        pathwise_predict_from_correction(
            fitted["x"], fitted["xq"], corr, rff1, st.params, bm=64, bn=64,
        )
