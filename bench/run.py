#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator it is started on.

    python bench/run.py --workload pol.fit_tol --seed 7 --seconds 20 --trace 0

The cell, its configuration, traffic mix, correctness limits and metrics
are found by name from ``BENCHMARK.json`` (see ``bench/spec.py``). The run
checks for a TPU with as many chips as the cell asks for and exits non-zero
without a result line otherwise. Set-up (start-up, data on the device,
compiling or loading every program from the persistent compilation cache in
``<checkout>/.jax_cache``, or ``$JAX_COMPILATION_CACHE_DIR`` where set) is
timed as ``setup_s``; then the traffic runs for ``--seconds``. After the
window the peak device memory is read, the program's state is freed and the
plain reference checks what the window produced.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the window in memory and reports its per-layer metrics,
with the device's busy time and a breakdown. The last line of standard
output is one JSON object; the numbers compared with their limits are also
the last lines of standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# The TPU runtime logs to /tmp/tpu_logs unless told otherwise; keep its
# logs in the run's own temporary directory.
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                  "tpu_logs"))


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache() -> str:
    """The program's persistent compilation cache
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``)."""
    import jax

    from repro.runtime import enable_compilation_cache

    cache = enable_compilation_cache()
    # Cache every program, the small eager ones too, so that a warm run
    # compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


class CompileCounter:
    """Counts programs traced, compiled or loaded from the cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if self.on and event in self.EVENTS:
            self.count += 1

    def _event(self, event, **kwargs):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def memory_peak(devices, temp_bytes) -> int:
    """The allocator's peak of live buffers (data, state, outputs) on the
    fullest chip, plus the temporary memory of the largest program the
    window ran (``temp_bytes()``), which the allocator's peak leaves out."""
    stats = [d.memory_stats() for d in devices]
    live = max(int(s["peak_bytes_in_use"]) for s in stats)
    temp = temp_bytes()
    log(f"[memory] live peak {live} + program temp {temp}; {stats[0]}")
    return live + temp


def start_trace():
    """A profiler session whose trace stays in memory: jax.profiler's own
    start/stop writes it to disk, which a run does not need."""
    from jaxlib import _profiler

    options = _profiler.ProfileOptions()
    options.python_tracer_level = 0
    return _profiler.ProfilerSession(options)


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, root: Path = ROOT) -> dict:
    """One run; returns the result line as a dict (``check`` last)."""
    import jax

    from bench.spec import load_cell
    from bench.trace_reduce import reduce

    cell = load_cell(workload, root=root)
    enable_cache()
    if require_tpu:
        device = device_info(cell.chips)
    else:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
    gen = cell.generator

    prep = gen.setup(cell, seed)
    counter = CompileCounter()
    counter.on = True
    setup_s = time.perf_counter() - T0
    session = start_trace() if trace else None
    win = gen.measure(prep, seconds)
    counter.on = False
    summary = profile = None
    if session is not None:
        t_trace = time.perf_counter()
        profile = session.stop_and_get_profile_data()
        t_read = time.perf_counter()
        summary = reduce(profile)
        log(f"[trace] collected in {t_read - t_trace:.1f} s, reduced in "
            f"{time.perf_counter() - t_read:.1f} s; the device trace misses "
            f"the window's last {summary.cut_s:.3f} s")
    log(f"[window] {win.attempted} fits, {win.steps} steps in "
        f"{win.seconds:.3f} s; programs compiled or loaded inside the "
        f"window: {counter.count}")
    used = jax.devices()[:cell.chips]
    if device["platform"] == "tpu":
        device["memory_peak_bytes"] = memory_peak(
            used, lambda: gen.program_temp_bytes(prep))

    # The profile stays in ctx for the per-layer readers, and goes after.
    ctx = {"cell": cell, "window": win, "setup_s": setup_s, "trace": summary,
           "profile": profile, "device_kind": device["kind"]}
    del profile
    metrics = {}
    entries = cell.per_layer if trace else cell.end_to_end
    for m in entries:
        value = cell.readers[m["name"]](ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    del ctx["profile"]

    t_check = time.perf_counter()
    compared = gen.compare(prep, win, cell.limits)
    log(f"[check] reference over {win.attempted} fits took "
        f"{time.perf_counter() - t_check:.1f} s")
    correct = win.failed == 0 and all(c.ok for c in compared)
    for c in compared:
        log(f"[check] {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    log(f"[check] correct={correct}")

    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["check"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in compared}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        log(f"[device] FAIL: {e}; nothing was run")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
