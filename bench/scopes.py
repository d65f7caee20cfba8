"""Device time of the outer step's phases, from a profile of the window.

The program names the parts of one outer step with ``jax.named_scope``:
``gp.targets``, ``gp.solve``, ``gp.precond``, ``gp.mvm``, ``gp.grad`` and
``gp.adam``. The names reach each compiled instruction's
``metadata={op_name="..."}``. Its driver opens a ``fit.chunk`` span, with a
``steps`` stat, around each ``outer_scan`` it runs, and ``fit.*`` spans
around its other phases. The names are written out here and not imported
from the program: a renamed scope then shows as a missing phase, not a
silently moved one.

* Each device operation of the profile is an instruction of a program: the
  ``XLA Modules`` line of its device plane has one event per execution of
  a program. The compiled text of every ``outer_scan`` the window ran
  (``programs``) maps each instruction to its ``op_name``. Where programs
  share a module name (one per chunk length), an execution takes the
  program whose instructions match the most of its operations.
* The window's i-th execution of such a program is its i-th ``fit.chunk``
  span: a fit runs one ``outer_scan`` per chunk and waits for it. They are
  paired by order and not by time, since the profiler aligns the host and
  device clocks only to about a millisecond. The sums cover the chunks that
  completed within the device trace (the trace holds a limited number of
  events, see ``bench.trace_reduce``): those whose span ended no more than
  ``CUT_TOLERANCE_NS`` after its last operation. They take every operation
  from the first such execution's start to the last one's end, those of
  other programs in between included, and the spans' ``steps`` stats count
  the steps.
* Each operation's self time goes to one phase, decided in this order:
  ``grad`` if ``gp.grad`` is anywhere in its path, else ``precond``
  (``gp.precond``), else ``solve_mvm`` (``gp.mvm`` under ``gp.solve``), else
  ``solve``, else ``targets``, else ``adam``. An operation with none of
  these (a copy, a loop's bookkeeping) takes the phase of the operation it
  runs inside; one that runs inside none is ``None``, not named.
* Each idle gap of the traced window (as ``bench.trace_reduce`` takes it)
  is put down to the innermost ``fit.*`` or ``bench.*`` span over its
  middle on the host.

A program without the scopes, or a driver without the spans, gives
``None``: there is nothing to read.

``window_phases`` reads the profile that ``bench/run.py`` keeps in
``ctx["profile"]`` until the per-layer readers have run, and the programs
from the cell's generator (``window_programs``, see ``bench.generators``).
"""
from __future__ import annotations

import contextlib
import re
import sys
import time
from dataclasses import dataclass, field

from bench.trace_reduce import (
    CUT_TOLERANCE_NS,
    DEVICE_PREFIX,
    HOST_PLANE,
    OP_LINE,
    short_name,
)

MODULE_LINE = "XLA Modules"
CHUNK_SPAN = "fit.chunk"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("fit.", "bench.")  # the spans idle time is put down to
# Phase -> the scopes that decide it, in the order they are tried.
PHASES = (
    ("grad", ("gp.grad",)),
    ("precond", ("gp.precond",)),
    ("solve_mvm", ("gp.mvm", "gp.solve")),
    ("solve", ("gp.solve",)),
    ("targets", ("gp.targets",)),
    ("adam", ("gp.adam",)),
)
_SCOPE = re.compile(r"gp\.[a-z]+")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule (\S+?),")


def phase_of(op_name: str) -> str | None:
    """The phase an instruction's ``op_name`` path puts it in, or None."""
    found = set(_SCOPE.findall(op_name))
    for phase, needs in PHASES:
        if found.issuperset(needs):
            return phase
    return None


@dataclass
class Program:
    """One compiled program: its module name, each instruction's op_name,
    and its instructions' short names (for telling programs apart)."""

    module: str
    op_names: dict = field(default_factory=dict)  # '%fusion.7' -> op_name
    shorts: set = field(default_factory=set)

    @classmethod
    def parse(cls, text: str) -> "Program":
        head = _MODULE.match(text)
        prog = cls(module=head.group(1) if head else "")
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            prog.shorts.add(short_name(line.strip().removeprefix("ROOT ")))
            name = _OP_NAME.search(line)
            if name:
                prog.op_names[m.group(1)] = name.group(1)
        return prog


@dataclass
class Phases:
    """Device seconds per phase over the chunks the trace holds, and idle
    seconds of the traced window per host span, averaged over the chips."""

    seconds: dict  # phase (None: not named) -> s
    busy_s: float
    steps: int
    chunks: int
    idle_s: dict = field(default_factory=dict)  # host span -> s

    def named_share(self) -> float:
        """Named phases over all device time of the chunks."""
        total = sum(self.seconds.values())
        named = total - self.seconds.get(None, 0.0)
        return named / total if total > 0 else 0.0


def _host_spans(profile):
    """The window ``(start, end)`` and the ``fit.*`` and ``bench.*`` spans
    inside it, as (start, end, name, stats) sorted by start, outer first."""
    host = [p for p in profile.planes if p.name == HOST_PLANE]
    found = []
    for line in host[0].lines if host else ():
        for ev in line.events:
            if ev.name.startswith(HOST_SPANS):
                found.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name, ev))
    window = next(((a, b) for a, b, name, _ in found if name == WINDOW_SPAN),
                  None)
    if window is None:
        return None, []
    lo, hi = window
    spans = [(a, b, name, dict(ev.stats) if name == CHUNK_SPAN else {})
             for a, b, name, ev in found if a >= lo and b <= hi]
    spans.sort(key=lambda s: (s[0], -s[1]))
    return window, spans


def _lines(plane, name):
    return [line for line in plane.lines if line.name == name]


def _match_programs(seen: dict, programs: list) -> dict:
    """Module event name -> the Program whose instructions match most of
    the operation names seen under it (same module name only)."""
    out = {}
    for module, names in seen.items():
        if module is None:
            continue
        base = module.split("(", 1)[0]
        shorts = {short_name(n) for n in names}
        best, score = None, 0
        for prog in programs:
            if prog.module != base:
                continue
            hit = len(shorts & prog.shorts)
            if hit > score:
                best, score = prog, hit
        if best is not None:
            out[module] = best
    return out


def _tag_modules(ops, mods):
    """(start, end, module, name) of each operation (sorted by start) with
    the program execution it starts in, and the names seen per module.
    Executions are ascending and do not overlap."""
    tagged, seen = [], {}
    m = 0
    for start, end, name in ops:
        while m < len(mods) and mods[m][1] <= start:
            m += 1
        module = mods[m][2] if m < len(mods) and mods[m][0] <= start else None
        seen.setdefault(module, set()).add(name)
        tagged.append((start, end, module, name))
    return tagged, seen


def _self_times(tagged, matched, seconds):
    """Add each operation's self time to its phase in ``seconds``; returns
    whether any operation named a phase itself."""
    own: dict = {}  # (module, op) -> the phase of its own op_name
    stack = []  # [end, phase, self time] of the enclosing operations
    any_scope = False
    for start, end, module, name in tagged:
        while stack and stack[-1][0] <= start:
            _, ph, self_ns = stack.pop()
            seconds[ph] += self_ns
        key = (module, name)
        if key not in own:
            prog = matched.get(module)
            op_name = "" if prog is None else prog.op_names.get(
                name.partition(" = ")[0], "")
            own[key] = phase_of(op_name)
        ph = own[key]
        any_scope = any_scope or ph is not None
        if stack:
            if ph is None:
                ph = stack[-1][1]
            stack[-1][2] -= end - start
        stack.append([end, ph, end - start])
    for _, ph, self_ns in stack:
        seconds[ph] += self_ns
    return any_scope


def _union(intervals):
    """Merged [start, end] of (start, end, ...) sorted by start."""
    busy = []
    for start, end, *_ in intervals:
        if busy and start <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], end)
        else:
            busy.append([start, end])
    return busy


def _idle_by_span(busy, spans, lo, hi, idle):
    """Add each gap of [lo, hi] between the ``busy`` intervals to the
    innermost span over its middle (spans nest; sorted by start)."""
    gaps, edge = [], lo
    for start, end in busy + [[hi, hi]]:
        if start > edge:
            gaps.append((edge, min(start, hi)))
        edge = max(edge, end)
    open_, i = [], 0
    for a, b in gaps:
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][0] <= mid:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s[1] > mid]
        name = open_[-1][2] if open_ else "(no span)"
        idle[name] = idle.get(name, 0.0) + (b - a)


def reduce_phases(profile, programs: list[str]) -> Phases | None:
    """Device seconds of each phase over the window's completed chunks
    (see the module's docstring); None where the trace holds no
    ``fit.chunk`` span or no operation carries a ``gp.*`` scope."""
    window, spans = _host_spans(profile)
    chunks = [s for s in spans if s[2] == CHUNK_SPAN]
    devices = [p for p in profile.planes if p.name.startswith(DEVICE_PREFIX)]
    if window is None or not chunks or not devices:
        return None
    lo, hi = window
    progs = [Program.parse(t) for t in programs]
    bases = {p.module for p in progs}

    planes = []
    last_op = lo
    for plane in devices:
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for line in _lines(plane, OP_LINE) for ev in line.events
               if lo - CUT_TOLERANCE_NS <= ev.start_ns < hi]
        ops.sort(key=lambda e: (e[0], -e[1]))
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for line in _lines(plane, MODULE_LINE)
                      for ev in line.events)
        planes.append((ops, mods))
        last_op = max([last_op] + [end for _, end, _ in ops])
    held = [c for c in chunks if c[1] <= last_op + CUT_TOLERANCE_NS]
    w_end = hi if len(held) == len(chunks) else last_op

    seconds: dict = {phase: 0.0 for phase, _ in PHASES}
    seconds[None] = 0.0
    idle: dict = {}
    busy = 0.0
    any_scope = False
    for ops, mods in planes:
        runs = [m for m in mods if m[2].split("(", 1)[0] in bases
                and lo - CUT_TOLERANCE_NS <= m[0] < hi][:len(held)]
        if runs:
            first, last = runs[0][0], runs[-1][1]
            tagged, seen = _tag_modules(
                [op for op in ops if first <= op[0] < last], mods)
            any_scope |= _self_times(tagged, _match_programs(seen, progs),
                                     seconds)
            busy += sum(b - a for a, b in _union(tagged))
        _idle_by_span(_union([(max(a, lo), min(b, w_end)) for a, b, _ in ops
                              if b > lo and a < w_end]),
                      spans, lo, w_end, idle)
    if not held or not any_scope:
        return None
    ndev = len(devices)
    return Phases(seconds={k: v / ndev * 1e-9 for k, v in seconds.items()},
                  busy_s=busy / ndev * 1e-9,
                  steps=int(sum(c[3].get("steps", 0) for c in held)),
                  chunks=len(held),
                  idle_s={k: v / ndev * 1e-9 for k, v in idle.items()})


@contextlib.contextmanager
def _fresh_compiles():
    """Compile anew, from neither JAX's in-memory caches nor the persistent
    cache. The persistent cache's key leaves out the ``op_name`` metadata,
    so a program cached by a build without the scopes would come back
    without them; the optimised program is the same either way."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def window_phases(ctx) -> Phases | None:
    """The phases of the run's traced window (``ctx["profile"]``), against
    the programs of the cell's generator; read once for all readers and
    kept in ``ctx["phases"]``."""
    if "phases" not in ctx:
        ctx["phases"] = _read_phases(ctx.get("profile"), ctx["cell"])
    return ctx["phases"]


def _read_phases(profile, cell) -> Phases | None:
    if profile is None:
        return None
    t0 = time.perf_counter()
    _, spans = _host_spans(profile)
    result = None
    if any(span[2] == CHUNK_SPAN for span in spans):
        with _fresh_compiles():
            texts = [lowered.compile().as_text()
                     for lowered in cell.generator.window_programs(cell)]
        t1 = time.perf_counter()
        result = reduce_phases(profile, texts)
        print(f"[phases] programs compiled in {t1 - t0:.1f} s, reduced in "
              f"{time.perf_counter() - t1:.1f} s", file=sys.stderr)
    if result is None:
        print("[phases] no fit.chunk span or gp.* scope in the trace: "
              "nothing to read", file=sys.stderr)
    else:
        print(f"[phases] {result.chunks} chunks, {result.steps} steps, "
              f"busy {result.busy_s!r} s, named "
              f"{100 * result.named_share()!r}%; device s: "
              + ", ".join(f"{k}={v!r}" for k, v in result.seconds.items())
              + "; idle s by host span: "
              + ", ".join(f"{k}={v!r}" for k, v in result.idle_s.items()),
              file=sys.stderr)
    return result

