"""Render structured JSONL event logs as per-trace waterfalls.

Consumes the files written by ``repro.obs.trace`` (replica request logs,
``fit(event_log=...)`` training logs, ``REPRO_OBS_LOG``) and prints:

  * a **per-trace waterfall** — every event carrying a trace ID, ordered by
    timestamp, with millisecond offsets from the trace's first event, so one
    request can be followed transport -> admission -> engine span -> reply
    (and, for appends, into the refresh that folded them in);
  * a **residual-decay summary** — for ``solve_step`` events that carry the
    solver ring (``SolverConfig.record_history``), the per-step first/last
    residual, the decay factor, and a coarse log10 sparkline of the
    trajectory; plus the closing ``fit_done`` totals;
  * a **budget-decision table** — for adaptive fits
    (``fit(budget_policy=...)``), the per-step ``budget_decision`` events
    rendered row-for-row with the ``solve_step`` table (same step/lane
    keys): allocated vs realised epochs, end residual, the calibrated
    decay rate, and the pool remaining (schema: ``docs/adaptive.md``).

Stdlib only, read-only, tolerant of truncated tail lines (a live log can be
mid-write).

``--fleet DIR`` merges every ``*.jsonl`` under DIR — per-replica request
logs AND the fleet monitor's alert log — into one time-ordered stream: the
waterfall keys on trace IDs where present, and a **fleet timeline** section
renders the monitor's ``slo_alert`` transitions (OK/WARN/PAGE, burn rates)
against the surrounding request activity.

Usage:
    python tools/trace_report.py [LOG.jsonl ...] [--fleet DIR]
        [--trace ID] [--kind KIND] [--limit N]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

# Fields already rendered in an event's fixed columns — everything else is
# shown as trailing key=value detail.
_SHOWN = {"ts", "kind", "trace_id", "dur_ms", "res_history"}
_SPARK = "▁▂▃▄▅▆▇█"


def load_events(paths):
    """All parseable events from ``paths``, each tagged with its source file."""
    events = []
    for path in paths:
        try:
            with open(path) as f:
                lines = f.readlines()
        except OSError as e:
            print(f"[trace-report] skipping {path}: {e}", file=sys.stderr)
            continue
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue  # live log mid-write: the tail line may be partial
            if isinstance(ev, dict) and "ts" in ev and "kind" in ev:
                ev["_src"] = path
                events.append(ev)
    events.sort(key=lambda e: e["ts"])
    return events


def _detail(ev) -> str:
    parts = []
    for k, v in ev.items():
        if k in _SHOWN or k.startswith("_") or v is None:
            continue
        if isinstance(v, float):
            v = f"{v:.4g}"
        parts.append(f"{k}={v}")
    return " ".join(parts)


def _sparkline(values) -> str:
    """Coarse log-scale sparkline (empty for <2 finite positive points)."""
    import math

    logs = [math.log10(v) for v in values if v and v > 0]
    if len(logs) < 2:
        return ""
    lo, hi = min(logs), max(logs)
    span = (hi - lo) or 1.0
    idx = [int((x - lo) / span * (len(_SPARK) - 1)) for x in logs]
    return "".join(_SPARK[i] for i in idx)


def print_waterfall(events, trace=None, limit=0):
    """One block per trace ID, events offset in ms from the trace's start."""
    traces: dict = {}
    for ev in events:
        tid = ev.get("trace_id")
        if tid is None or (trace is not None and tid != trace):
            continue
        traces.setdefault(tid, []).append(ev)
    if not traces:
        print("no traced events" + (f" for trace {trace!r}" if trace else ""))
        return
    shown = 0
    for tid, evs in traces.items():
        if limit and shown >= limit:
            print(f"... {len(traces) - shown} more traces (raise --limit)")
            break
        shown += 1
        t0 = evs[0]["ts"]
        span_ms = (evs[-1]["ts"] - t0) * 1e3
        print(f"trace {tid}  ({len(evs)} events, {span_ms:.1f}ms)")
        for ev in evs:
            off = (ev["ts"] - t0) * 1e3
            dur = ev.get("dur_ms")
            dur_s = f" [{dur:.2f}ms]" if isinstance(dur, (int, float)) else ""
            print(f"  +{off:9.2f}ms  {ev['kind']:<10}{dur_s:<12} "
                  f"{_detail(ev)}")
        print()


def print_residual_summary(events):
    """Convergence table from solve_step rings + the fit_done totals."""
    steps = [e for e in events if e["kind"] == "solve_step"]
    if steps:
        print("residual decay (solve_step):")
        print(f"  {'step':>4} {'solver':<6} {'lane':>4} {'iters':>5} "
              f"{'first_res':>10} {'last_res':>10} {'decay':>9}  trajectory")
        for ev in steps:
            ring = ev.get("res_history") or []
            res = [row[0] for row in ring if isinstance(row, (list, tuple))]
            first = res[0] if res else ev.get("res_y")
            last = res[-1] if res else ev.get("res_y")
            decay = (last / first) if first else float("nan")
            lane = ev.get("lane")
            print(f"  {ev.get('step', -1):>4} {ev.get('solver', '?'):<6} "
                  f"{'-' if lane is None else lane:>4} "
                  f"{ev.get('iters', 0):>5} {first:>10.3e} {last:>10.3e} "
                  f"{decay:>9.2e}  {_sparkline(res)}")
    print_budget_summary(events)
    for ev in events:
        if ev["kind"] == "fit_done":
            print(f"fit_done: solver={ev.get('solver')} "
                  f"steps={ev.get('num_steps')} iters={ev.get('total_iters')} "
                  f"epochs={ev.get('total_epochs'):.1f} "
                  f"wall={ev.get('wall_time_s'):.2f}s")


def fleet_logs(fleet_dir):
    """Every ``*.jsonl`` under ``fleet_dir`` (one level), sorted.

    The layout ``--request-log`` + ``--monitor`` produce: per-replica
    ``replica_*.jsonl`` request logs next to the monitor's
    ``monitor.jsonl`` alert log.
    """
    return sorted(glob.glob(os.path.join(fleet_dir, "*.jsonl")))


def print_fleet_timeline(events, limit=0):
    """The fleet view: ``slo_alert`` transitions in request context.

    Renders every monitor alert (state change, burn rates) in one
    time-ordered table, each annotated with how many requests landed in
    the preceding inter-alert gap — enough to read "traffic stopped, then
    availability paged" straight off the report. Traced request detail
    stays in the per-trace waterfall above.
    """
    alerts = [e for e in events if e["kind"] == "slo_alert"]
    if not alerts:
        return
    requests = [e["ts"] for e in events if e["kind"] == "request"]
    t0 = events[0]["ts"]
    print(f"fleet timeline ({len(alerts)} alert(s), "
          f"{len(requests)} request(s)):")
    prev = t0
    shown = 0
    for ev in alerts:
        if limit and shown >= limit:
            print(f"  ... {len(alerts) - shown} more alerts (raise --limit)")
            break
        shown += 1
        n_req = sum(1 for ts in requests if prev <= ts < ev["ts"])
        burns = ev.get("burn_rates") or {}
        burn_s = " ".join(f"{k}={v:.3g}" for k, v in sorted(burns.items()))
        print(f"  +{(ev['ts'] - t0) * 1e3:9.1f}ms  "
              f"{ev.get('slo', '?'):<14} "
              f"{ev.get('from_state', '?'):>4} -> {ev.get('to_state', '?'):<4} "
              f"({n_req} requests since last alert) {burn_s}")
        prev = ev["ts"]
    print()


def print_budget_summary(events):
    """Adaptive-controller table from ``budget_decision`` events.

    Rows carry the same ``(step, lane)`` keys as the ``solve_step`` table
    above them, so the two read side by side: what the controller
    allocated, what the solve realised, and the calibrated state it left
    behind.
    """
    decisions = [e for e in events if e["kind"] == "budget_decision"]
    if not decisions:
        return

    def f(ev, key, width=9):
        v = ev.get(key)
        return f"{v:>{width}.3g}" if isinstance(v, (int, float)) else \
            f"{'-':>{width}}"

    print("budget decisions (budget_decision):")
    print(f"  {'step':>4} {'solver':<6} {'lane':>4} {'alloc':>9} "
          f"{'realised':>9} {'pred_tol':>9} {'res':>9} {'slope':>9} "
          f"{'pool':>9}")
    for ev in decisions:
        lane = ev.get("lane")
        print(f"  {ev.get('step', -1):>4} {ev.get('solver', '?'):<6} "
              f"{'-' if lane is None else lane:>4} {f(ev, 'alloc')} "
              f"{f(ev, 'realised')} {f(ev, 'pred_to_tol')} {f(ev, 'res')} "
              f"{f(ev, 'slope')} {f(ev, 'pool')}")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("logs", nargs="*", help="JSONL event logs")
    ap.add_argument("--fleet", default=None, metavar="DIR",
                    help="merge every *.jsonl under DIR (replica request "
                         "logs + the monitor's alert log) and render the "
                         "fleet timeline")
    ap.add_argument("--trace", default=None,
                    help="show only this trace ID's waterfall")
    ap.add_argument("--kind", default=None,
                    help="keep only events of this kind")
    ap.add_argument("--limit", type=int, default=20,
                    help="max traces in the waterfall (0 = all)")
    args = ap.parse_args(argv)

    paths = list(args.logs)
    if args.fleet:
        found = fleet_logs(args.fleet)
        if not found:
            print(f"no *.jsonl logs under {args.fleet}", file=sys.stderr)
        paths.extend(found)
    if not paths:
        ap.error("no logs given (pass LOG.jsonl files and/or --fleet DIR)")

    events = load_events(paths)
    if args.kind:
        events = [e for e in events if e["kind"] == args.kind]
    if not events:
        print("no events parsed")
        return 1
    kinds: dict = {}
    for e in events:
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
    print(f"{len(events)} events from {len(paths)} log(s): "
          + ", ".join(f"{k}={n}" for k, n in sorted(kinds.items())))
    print()
    if args.fleet:
        print_fleet_timeline(events, limit=args.limit)
    print_waterfall(events, trace=args.trace, limit=args.limit)
    print_residual_summary(events)
    return 0


if __name__ == "__main__":
    sys.exit(main())
