"""CLI serving driver (reduced configs on local devices).

LM archs: autoregressive generation with the KV/SSM cache serve_step.
GP arch: pathwise-conditioning prediction server on `repro.serve` — fit,
export a `ServableGP`, drive the shape-bucketed engine (zero linear solves
per request, eq. 16 amortisation; zero retraces after warmup). `--compat`
keeps the legacy per-request loop (jit hoisted out of the loop, tail block
padded).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.runtime import enable_compilation_cache


def serve_lm(args):
    from repro.configs import get_config
    from repro.models import init_cache, init_params, make_serve_step
    from repro.models.transformer import prefill_cross_cache

    cfg = get_config(args.arch, smoke=True)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    b, steps = args.batch, args.tokens
    max_len = args.max_len
    enc_len = 32 if cfg.is_encdec else 0
    cache = init_cache(cfg, b, max_len, enc_len=enc_len)
    if cfg.is_encdec:
        frames = jax.random.normal(key, (b, enc_len, cfg.d_model)) * 0.3
        cache = prefill_cross_cache(params, cfg, frames, cache)
    step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    toks = jnp.zeros((b,), jnp.int32)
    t0 = time.perf_counter()
    out = []
    for pos in range(steps):
        logits, cache = step(params, cache, toks, jnp.asarray(pos, jnp.int32))
        toks = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
        out.append(toks)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    print(f"[serve] {args.arch}: {steps} steps x batch {b} in {dt:.2f}s "
          f"({steps*b/dt:.1f} tok/s); sample row: "
          f"{[int(t[0]) for t in out[:16]]}")


def _fit_gp(args):
    from repro.core import OuterConfig, fit
    from repro.data.synthetic import load_dataset
    from repro.solvers import SolverConfig

    ds = load_dataset(args.dataset, max_n=args.max_n)
    cfg = OuterConfig(
        estimator="pathwise", warm_start=True, num_probes=32,
        solver=SolverConfig(name="cg", max_epochs=100, precond_rank=0),
        num_steps=args.train_steps, bm=512, bn=512,
    )
    res = fit(ds.x_train, ds.y_train, cfg, key=jax.random.PRNGKey(args.seed))
    return ds, cfg, res.state


def serve_gp_compat(args, ds, cfg, state):
    """Legacy per-request loop, minimally fixed: the `pathwise_predict` jit
    is built ONCE outside the request loop, and the tail block is padded to
    the fixed request width so ragged shapes never retrace."""
    from functools import partial

    from repro.core import pathwise_predict, predictive_metrics

    width = 64
    predict = jax.jit(partial(
        pathwise_predict, kind=None, bm=cfg.bm, bn=cfg.bn
    ))
    n_test = ds.x_test.shape[0]
    t0 = time.perf_counter()
    for i in range(args.requests):
        lo = (i * width) % max(1, n_test)
        xq = ds.x_test[lo : lo + width]
        take = xq.shape[0]
        if take < width:  # pad the tail block instead of wrapping/retracing
            xq = jnp.pad(xq, ((0, width - take), (0, 0)))
        pred = predict(ds.x_train, xq, state.carry_v, state.probes,
                       state.params)
        jax.block_until_ready(pred.mean)
    dt = time.perf_counter() - t0
    m = predictive_metrics(ds.y_test[:width],
                           pathwise_predict(ds.x_train, ds.x_test[:width],
                                            state.carry_v, state.probes,
                                            state.params),
                           state.params)
    print(f"[serve-gp compat] {args.requests} requests x {width} in {dt:.2f}s "
          f"({args.requests*width/dt:.1f} q/s) — ZERO solves at serve time; "
          f"rmse={float(m['rmse']):.4f} llh={float(m['llh']):.4f}")


def _metrics_smoke_probe(endpoints, xq):
    """Observability leg of the CI smoke: a /predict carrying an explicit
    ``X-Trace-Id`` must echo it back, and GET /metrics must serve Prometheus
    text exposing the request/admission/engine metric families."""
    import json as _json
    import urllib.request

    import numpy as np

    from repro.obs import trace as obs_trace

    required = (
        "gp_http_requests_total",
        "gp_admission_decisions_total",
        "gp_engine_batch_seconds",
        "gp_engine_queue_depth",
    )
    probe = _json.dumps({"x": np.asarray(xq).tolist()}).encode()
    for ep in endpoints:
        tid = "smoke-" + obs_trace.new_trace_id()
        req = urllib.request.Request(
            ep + "/predict", data=probe,
            headers={"Content-Type": "application/json",
                     obs_trace.TRACE_HEADER: tid})
        with urllib.request.urlopen(req, timeout=30) as resp:
            echoed = resp.headers.get(obs_trace.TRACE_HEADER)
        if echoed != tid:
            raise SystemExit(
                f"[obs-smoke] {ep} trace header not echoed: sent {tid!r}, "
                f"got {echoed!r}")
        with urllib.request.urlopen(ep + "/metrics", timeout=10) as resp:
            ctype = resp.headers.get("Content-Type", "")
            text = resp.read().decode()
        if "version=0.0.4" not in ctype:
            raise SystemExit(f"[obs-smoke] {ep}/metrics content type {ctype!r}")
        missing = [f for f in required if f"# TYPE {f} " not in text]
        if missing:
            raise SystemExit(
                f"[obs-smoke] {ep}/metrics missing families {missing}; "
                f"got {len(text)} bytes")
        print(f"[obs-smoke] {ep}: trace echo ok, /metrics ok "
              f"({len(text.splitlines())} lines)")


def _fleet_smoke_probe(sup, monitor, monitor_ep, endpoints, xq):
    """The fleet-observability CI smoke against a live cluster + monitor.

    Sequence: every replica must show up on ``/fleet/health``; after a
    burst of traffic the aggregated ``/fleet/metrics`` ``/predict``
    counters must EQUAL the per-replica ``/metrics`` totals (exact — the
    scraper re-exports samples verbatim); ``/fleet/health`` EWMA/shed-rate
    must match each replica's own ``/stats``; then one replica is
    hard-killed and ``gp_fleet_replica_up`` must flip to 0 within a couple
    of scrape intervals, with the availability burn-rate rule escalating
    to PAGE. Raises SystemExit on any violation.
    """
    import urllib.request

    import numpy as np

    from repro.obs.scrape import parse_prometheus
    from repro.serve.cluster.replica import _http_json

    interval = monitor.interval_s

    def wait_for(pred, timeout_s, what):
        deadline = time.monotonic() + timeout_s
        t0 = time.monotonic()
        while time.monotonic() < deadline:
            try:
                if pred():
                    return time.monotonic() - t0
            except OSError:
                pass
            time.sleep(max(0.05, interval / 4))
        raise SystemExit(f"[fleet-smoke] timed out waiting for {what}")

    names = [f"replica_{i}" for i in range(len(endpoints))]

    # 1. Every replica reports up on /fleet/health.
    def all_up():
        status, h = _http_json(monitor_ep + "/fleet/health")
        return status == 200 and h["num_up"] == len(endpoints)

    wait_for(all_up, 30 * interval + 30, "all replicas up on /fleet/health")
    print(f"[fleet-smoke] {len(endpoints)} replicas up on /fleet/health")

    # 2. Traffic: a burst of predicts against every replica, then stop —
    # quiescent counters are what makes the exactness check exact.
    probe = {"x": np.asarray(xq).tolist()}
    for _ in range(5):
        for ep in endpoints:
            status, body = _http_json(ep + "/predict", probe)
            if status not in (200, 429):
                raise SystemExit(
                    f"[fleet-smoke] {ep}/predict -> {status}: {body}")

    def parse_url(url):
        with urllib.request.urlopen(url, timeout=10) as resp:
            return parse_prometheus(resp.read().decode("utf-8"))

    def predict_total(fams, where=None):
        fam = fams.get("gp_http_requests_total")
        total = 0.0
        for s in (fam.samples if fam else ()):
            if s.labels.get("path") != "/predict":
                continue
            if where is None or where(s.labels):
                total += s.value
        return total

    direct = {
        name: predict_total(parse_url(ep + "/metrics"))
        for name, ep in zip(names, endpoints)
    }

    # 3. /fleet/metrics totals must EQUAL the per-replica counters once the
    # scraper's cache catches up (a couple of intervals at most).
    def fleet_matches():
        fams = parse_url(monitor_ep + "/fleet/metrics")
        got = {
            name: predict_total(
                fams, where=lambda lbl, n=name: lbl.get("replica") == n)
            for name in names
        }
        return got == direct

    wait_for(fleet_matches, 10 * interval + 30,
             f"/fleet/metrics to equal per-replica totals {direct}")
    print(f"[fleet-smoke] /fleet/metrics == per-replica /metrics: {direct}")

    # 4. /fleet/health load signals must match each replica's own /stats.
    def health_matches():
        _, h = _http_json(monitor_ep + "/fleet/health")
        for name, ep in zip(names, endpoints):
            entry = h["replicas"].get(name)
            if entry is None:
                return False
            _, stats = _http_json(ep + "/stats")
            adm = stats["admission"]
            admitted, shed = adm.get("admitted", 0), adm.get("shed", 0)
            want_shed = shed / (admitted + shed) if (admitted + shed) else 0.0
            got_ewma = entry["service_ewma_ms"]
            if got_ewma is None or \
                    abs(got_ewma - adm["service_ewma_ms"]) > 1e-9:
                return False
            if abs((entry["shed_rate"] or 0.0) - want_shed) > 1e-9:
                return False
        return True

    wait_for(health_matches, 10 * interval + 30,
             "/fleet/health EWMA/shed-rate to match replica /stats")
    print("[fleet-smoke] /fleet/health EWMA + shed-rate match /stats")

    # 5. Availability must settle at OK before the chaos step.
    def avail_ok():
        _, s = _http_json(monitor_ep + "/fleet/slo")
        return s["slos"].get("availability", {}).get("state") == "OK"

    wait_for(avail_ok, 60 * interval + 30, "availability SLO to settle OK")

    # 6. Chaos: hard-kill the last replica. Up must flip within ~2 scrape
    # intervals; the availability burn rate must escalate OK -> PAGE.
    victim = len(endpoints) - 1
    sup.kill(victim)
    t_kill = time.monotonic()

    def victim_down():
        _, h = _http_json(monitor_ep + "/fleet/health")
        entry = h["replicas"].get(names[victim])
        return entry is not None and not entry["up"]

    took = wait_for(victim_down, 4 * interval + 15,
                    f"gp_fleet_replica_up 0 for {names[victim]}")
    print(f"[fleet-smoke] {names[victim]} marked down "
          f"{took:.1f}s after kill (interval {interval}s)")

    def paged():
        _, s = _http_json(monitor_ep + "/fleet/slo")
        return s["slos"].get("availability", {}).get("state") == "PAGE"

    slow = max(r.slow_window_s
               for slo in monitor.slo_engine._states.values()
               for r in slo.slo.rules)
    wait_for(paged, slow + 60 * interval + 30,
             "availability burn-rate PAGE after replica kill")
    print(f"[fleet-smoke] availability PAGE "
          f"{time.monotonic() - t_kill:.1f}s after kill — OK")


def _http_smoke_probe(endpoints, xq, metrics=False):
    """The CI smoke sequence against live endpoints: /healthz and /predict
    must 200 with finite predictions; a flood past the admission cap must
    shed 429 WITH a Retry-After hint. Raises SystemExit on any violation."""
    import numpy as np

    from repro.serve.cluster.replica import _http_json

    for ep in endpoints:
        status, body = _http_json(ep + "/healthz")
        if status != 200:
            raise SystemExit(f"[http-smoke] {ep}/healthz -> {status}: {body}")
        status, body = _http_json(ep + "/predict",
                                  {"x": np.asarray(xq).tolist()})
        if status != 200:
            raise SystemExit(f"[http-smoke] {ep}/predict -> {status}: {body}")
        mean = np.asarray(body["mean"])
        if mean.shape != (xq.shape[0],) or not np.all(np.isfinite(mean)):
            raise SystemExit(f"[http-smoke] non-finite/misshapen mean: {body}")
        print(f"[http-smoke] {ep}: healthz ok, predict ok "
              f"(version={body.get('version')})")

    # Flood one endpoint past the admission cap: sequential requests drain
    # the token bucket, so with burst B requests B+1.. must shed.
    import urllib.error
    import urllib.request
    import json as _json

    ep = endpoints[0]
    codes, retry_after = [], None
    probe = _json.dumps({"x": np.asarray(xq[:1]).tolist()}).encode()
    for _ in range(10):
        req = urllib.request.Request(
            ep + "/predict", data=probe,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                codes.append(resp.status)
        except urllib.error.HTTPError as e:
            codes.append(e.code)
            if e.code == 429 and retry_after is None:
                retry_after = e.headers.get("Retry-After")
    if 429 not in codes:
        raise SystemExit(f"[http-smoke] flood never shed: {codes}")
    if retry_after is None or int(retry_after) < 1:
        raise SystemExit(f"[http-smoke] 429 without Retry-After: {codes}")
    stats_status, stats = _http_json(ep + "/stats")
    if stats_status != 200 or stats["admission"]["shed"] < codes.count(429):
        raise SystemExit(f"[http-smoke] stats disagree with flood: {stats}")
    if "schema_version" not in stats or "ts" not in stats:
        raise SystemExit(f"[http-smoke] /stats missing ts/schema_version: "
                         f"{sorted(stats)}")
    print(f"[http-smoke] flood codes={codes} Retry-After={retry_after} "
          f"shed={stats['admission']['shed']} — OK")
    if metrics:
        _metrics_smoke_probe(endpoints, xq)


def serve_gp_http(args, ds, cfg, state):
    """HTTP cluster serving: publish the artifact, run 1..N replicas.

    ``--replicas 1`` without ``--artifact-store`` serves in-process (no
    extra processes, still the full transport/admission stack). With a
    store, replicas are spawned worker processes that poll ``LATEST`` and
    pick up every later publish without a restart.
    """
    from repro.serve import MultiModelServer, export_servable
    from repro.serve.cluster import (
        AdmissionController,
        ReplicaSupervisor,
        ServeFrontend,
        publish_servable,
        start_http_server,
    )

    host, port = args.http.rsplit(":", 1)
    port = int(port)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    model = export_servable(state, ds.x_train)
    width = min(16, ds.x_test.shape[0])
    xq = ds.x_test[:width]

    if args.replicas > 1 and not args.artifact_store:
        raise SystemExit("--replicas > 1 needs --artifact-store (the store "
                         "is how worker processes receive the model)")
    if args.fleet_smoke and not (args.artifact_store and args.monitor):
        raise SystemExit("--fleet-smoke needs --artifact-store (supervised "
                         "replicas) and --monitor HOST:PORT")

    if args.artifact_store:
        version = publish_servable(args.artifact_store, model)
        print(f"[serve-http] published {version} -> {args.artifact_store}")
        sup = ReplicaSupervisor(
            args.artifact_store, num_replicas=args.replicas, host=host,
            base_port=port, buckets=buckets, bm=cfg.bm, bn=cfg.bn,
            rate_qps=args.admission_qps, burst=args.admission_burst,
            max_inflight=args.max_inflight,
            request_log_dir=args.request_log,
        )
        endpoints = sup.start()
        print(f"[serve-http] {args.replicas} replica(s): {endpoints}")

        monitor = monitor_server = None
        if args.monitor:
            import os

            from repro.obs.trace import EventLog
            from repro.serve.cluster.monitor import (
                FleetMonitor,
                default_slos,
                start_monitor_server,
            )

            mhost, mport = args.monitor.rsplit(":", 1)
            interval = args.monitor_interval
            slos = None
            if args.fleet_smoke:
                # Short windows so the burn-rate PAGE fires within the
                # smoke's patience rather than the production 5min/1h.
                interval = min(interval, 0.5)
                slos = default_slos(fast_window_s=6 * interval,
                                    slow_window_s=18 * interval)
            mlog = None
            if args.request_log:
                os.makedirs(args.request_log, exist_ok=True)
                mlog = EventLog(
                    path=os.path.join(args.request_log, "monitor.jsonl"))
            monitor = FleetMonitor(
                supervisor=sup, interval_s=interval, slos=slos,
                event_log=mlog)
            monitor_server, _ = start_monitor_server(
                monitor, host=mhost, port=int(mport))
            monitor_ep = f"http://{mhost}:{monitor_server.port}"
            print(f"[serve-http] fleet monitor: {monitor_ep}/fleet/"
                  f"{{metrics,slo,health}} (interval {interval}s)")

        try:
            if args.fleet_smoke:
                if monitor is None:
                    raise SystemExit("--fleet-smoke needs --monitor HOST:PORT")
                _fleet_smoke_probe(sup, monitor, monitor_ep, endpoints, xq)
            elif args.http_smoke:
                _http_smoke_probe(endpoints, xq, metrics=args.metrics)
            elif args.serve_seconds:
                time.sleep(args.serve_seconds)
            else:
                while True:
                    time.sleep(3600)
        except KeyboardInterrupt:
            pass
        finally:
            if monitor_server is not None:
                monitor_server.shutdown()
                monitor.stop()
            sup.stop()
        return

    if args.request_log:
        # In-process replica: one log file, same layout the supervisor uses.
        import os

        from repro.obs import trace as obs_trace

        os.makedirs(args.request_log, exist_ok=True)
        obs_trace.configure(
            path=os.path.join(args.request_log, "replica_0.jsonl"))

    server = MultiModelServer(buckets=buckets, bm=cfg.bm, bn=cfg.bn)
    server.register("default", model, warmup=True)
    admission = AdmissionController(
        buckets=buckets, rate_qps=args.admission_qps,
        burst=args.admission_burst, max_inflight=args.max_inflight,
    )
    online = None
    if args.refresh_every:
        # In-place refresh replica: expose the refresher's counters
        # (escalations, coupling residuals, capacity growth) on GET /stats.
        from repro.serve import OnlineGP

        online = OnlineGP(ds.x_train, ds.y_train, state, cfg)
    frontend = ServeFrontend(server, admission, refresh_source=online)
    httpd, _ = start_http_server(frontend, host=host, port=port)
    endpoint = f"http://{host}:{httpd.port}"
    print(f"[serve-http] in-process replica: {endpoint}")
    try:
        if args.http_smoke:
            _http_smoke_probe([endpoint], xq, metrics=args.metrics)
        elif args.serve_seconds:
            time.sleep(args.serve_seconds)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        httpd.shutdown()


def serve_gp(args, ds=None, cfg=None, state=None):
    """Engine-based serving: fit -> export `ServableGP` -> bucketed engine.

    Steady state is zero retraces (all bucket executables compiled by
    `warmup`) and zero linear solves (eq. 16 amortisation via the frozen
    correction matrix).
    """
    import numpy as np

    from repro.core import predictive_metrics
    from repro.serve import BucketedEngine, OnlineGP, export_servable

    if ds is None:
        ds, cfg, state = _fit_gp(args)
    if args.compat:
        return serve_gp_compat(args, ds, cfg, state)
    if args.http:
        return serve_gp_http(args, ds, cfg, state)

    buckets = tuple(int(b) for b in args.buckets.split(","))
    model = export_servable(state, ds.x_train)
    engine = BucketedEngine(model, buckets=buckets, bm=cfg.bm, bn=cfg.bn)
    compiles = engine.warmup()

    width = 64
    n_test = ds.x_test.shape[0]
    lat = []
    t0 = time.perf_counter()
    for i in range(args.requests):
        lo = (i * width) % max(1, n_test - 1)
        xq = ds.x_test[lo : lo + width]
        ts = time.perf_counter()
        pred = engine.submit(xq)
        jax.block_until_ready(pred.mean)
        lat.append(time.perf_counter() - ts)
    dt = time.perf_counter() - t0
    now = engine.num_compiles()
    retraces = None if (compiles is None or now is None) else now - compiles

    if args.refresh_every and n_test > 0:
        blk = min(width, n_test)
        online = OnlineGP(ds.x_train, ds.y_train, state, cfg)
        online.append(ds.x_test[:blk], ds.y_test[:blk])
        report = online.refresh_into(engine, budget_epochs=10.0)
        print(f"[serve-gp] online refresh: +{blk} rows -> n={report.n}, "
              f"{report.epochs:.1f} epochs, res_y={report.res_y:.3f}")

    m = predictive_metrics(
        ds.y_test[:width], engine.submit(ds.x_test[:width]), state.params
    )
    p50, p99 = np.percentile(np.asarray(lat) * 1e3, [50, 99])
    retrace_msg = "n/a (no cache introspection)" if retraces is None else retraces
    print(f"[serve-gp] {args.requests} requests x {width} in {dt:.2f}s "
          f"({args.requests*width/dt:.1f} q/s, p50={p50:.1f}ms p99={p99:.1f}ms) "
          f"— buckets={buckets}, retraces after warmup={retrace_msg}, "
          f"ZERO solves at serve time; "
          f"rmse={float(m['rmse']):.4f} llh={float(m['llh']):.4f}")
    if retraces:
        raise SystemExit(f"steady-state serving retraced {retraces}x")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gp-iterative")
    ap.add_argument("--dataset", default="pol")
    ap.add_argument("--max-n", type=int, default=2000)
    ap.add_argument("--train-steps", type=int, default=10)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--buckets", default="16,64,256",
                    help="comma-separated GP engine row buckets")
    ap.add_argument("--compat", action="store_true",
                    help="legacy per-request GP loop (jit hoisted, tail padded)")
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="if set, run one warm online refresh after serving")
    ap.add_argument("--http", default=None, metavar="HOST:PORT",
                    help="serve GP predictions over HTTP (port 0 = ephemeral)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="worker processes behind --http (>1 needs "
                         "--artifact-store; replica i binds PORT+i)")
    ap.add_argument("--artifact-store", default=None, metavar="DIR",
                    help="publish the fitted artifact here and serve from it "
                         "(replicas poll LATEST and hot-swap new publishes)")
    ap.add_argument("--admission-qps", type=float, default=None,
                    help="admitted requests/s per bucket class (None = no "
                         "rate limit)")
    ap.add_argument("--admission-burst", type=float, default=None,
                    help="token-bucket burst (default 2x qps)")
    ap.add_argument("--max-inflight", type=int, default=64,
                    help="concurrent in-compute requests before shedding")
    ap.add_argument("--serve-seconds", type=float, default=0,
                    help="serve for S seconds then exit (0 = run forever)")
    ap.add_argument("--http-smoke", action="store_true",
                    help="probe /healthz + /predict + overload shedding "
                         "against the live server, then exit (CI smoke)")
    ap.add_argument("--metrics", action="store_true",
                    help="with --http-smoke: also assert X-Trace-Id echo and "
                         "the Prometheus families on GET /metrics")
    ap.add_argument("--request-log", default=None, metavar="DIR",
                    help="write per-replica structured JSONL request logs "
                         "(request/admission/engine span events) under DIR")
    ap.add_argument("--monitor", default=None, metavar="HOST:PORT",
                    help="run the fleet monitor alongside the supervisor "
                         "(scrapes every replica, serves /fleet/metrics, "
                         "/fleet/slo, /fleet/health; port 0 = ephemeral)")
    ap.add_argument("--monitor-interval", type=float, default=1.0,
                    help="monitor scrape/evaluate period in seconds")
    ap.add_argument("--fleet-smoke", action="store_true",
                    help="probe the fleet plane (aggregate==per-replica "
                         "counters, health contract, kill-one-replica "
                         "staleness + burn-rate PAGE), then exit (CI smoke)")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    if args.arch == "gp-iterative":
        serve_gp(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
