"""Reduce a profiler trace to device busy time, top operations and idle gaps.

Input is a ``jax.profiler.ProfileData`` (from a live session or an
``.xplane.pb`` file). On a TPU the trace has one plane per chip,
``/device:TPU:<i>``, whose ``XLA Ops`` line holds one event per operation
executed; control flow nests (a ``while`` event spans its body's events).
The host plane ``/host:CPU`` has a line per thread; the benchmark's own
spans sit on the thread that made them.

* The window is the host span named ``window_span``. The device's trace
  holds a limited number of events: where a ``fit_span`` (a fit, which
  returns only once the device has run all of its work) ends more than
  ``CUT_TOLERANCE_NS`` after the last operation the trace holds, the trace
  was cut short, and the traced window ends at that operation
  (``cut_s`` says how much of the window the trace does not cover).
* Busy time is the union of a chip's operation intervals inside the window,
  averaged over the chips.
* An operation's time is its self time (its interval less the operations
  nested in it), summed over executions and averaged over the chips; the
  ``TOP`` largest are reported under a short name (instruction, output
  type and opcode, layouts dropped).
* An idle gap is an interval of the window in which a chip runs no
  operation. Each of the ``TOP`` longest is named after the innermost event
  of the benchmark's thread that covers the gap's middle: what the host was
  doing. Device and host clocks are aligned by the profiler to within about
  a millisecond, so a gap shorter than that may carry the wrong name.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10
CUT_TOLERANCE_NS = 10_000_000
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclass
class TraceSummary:
    """What the per-layer readers and the result line take from a trace."""

    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    device_ops: list = field(default_factory=list)  # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)  # [[host activity, s]]
    cut_s: float = 0.0  # the window's end that the device trace misses


def short_name(hlo: str) -> str:
    """'%fusion.7 = f32[8,8]{1,0} fusion(...), ...' -> '%fusion.7 = f32[8,8]
    fusion'."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    rest = _LAYOUT.sub("", rest)
    if not rest.startswith("("):
        rest = rest.split("(", 1)[0]
    else:  # tuple-shaped result: keep the opcode only
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = "(...) " + rest[i + 1:].split("(", 1)[0].strip()
                break
    return f"{head} = {rest.strip()}"[:120]


def _events(line, lo: int, hi: int):
    """(start, end, name) of ``line``'s events, clipped to [lo, hi]."""
    out = []
    for ev in line.events:
        start = ev.start_ns
        end = start + ev.duration_ns
        if end > lo and start < hi:
            out.append((max(start, lo), min(end, hi), ev.name))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def _busy_and_self(events):
    """Union of the intervals, and each operation's self time."""
    busy = []
    self_ns: dict[str, float] = {}
    stack = []  # [end, name, child time] of the enclosing events
    for start, end, name in events:
        while stack and stack[-1][0] <= start:
            e_end, e_name, child = stack.pop()
            self_ns[e_name] = self_ns.get(e_name, 0.0) + child
        if stack:
            stack[-1][2] -= end - start
        stack.append([end, name, end - start])
        if busy and start <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], end)
        else:
            busy.append([start, end])
    for _, name, child in stack:
        self_ns[name] = self_ns.get(name, 0.0) + child
    return busy, self_ns


def _host_label(events, lo: float, hi: float) -> str:
    """Innermost host event that covers the middle of [lo, hi]."""
    mid = 0.5 * (lo + hi)
    best = None
    for start, end, name in events:
        if start <= mid <= end and (best is None
                                    or end - start < best[1] - best[0]):
            best = (start, end, name)
    return best[2] if best is not None else "(no host event)"


def reduce(profile, window_span: str = "bench.window",
           fit_span: str = "bench.fit") -> TraceSummary:
    """Busy time, top device operations and longest idle gaps."""
    host = [p for p in profile.planes if p.name == HOST_PLANE]
    if not host:
        raise ValueError("the trace has no host plane")
    window = None
    for line in host[0].lines:
        for ev in line.events:
            if ev.name == window_span:
                window = (ev.start_ns, ev.start_ns + ev.duration_ns, line)
                break
        if window:
            break
    if window is None:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi, host_line = window
    devices = [p for p in profile.planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("the trace has no TPU device plane")

    per_device = []
    for plane in devices:
        ops = [e for line in plane.lines if line.name == OP_LINE
               for e in _events(line, lo, hi)]
        ops.sort(key=lambda e: (e[0], -e[1]))
        per_device.append(ops)
    host_events = _events(host_line, lo, hi)
    last_op = max((end for ops in per_device for _, end, _ in ops),
                  default=lo)
    last_fit = max((end for _, end, name in host_events if name == fit_span),
                   default=lo)
    cut = last_fit - last_op > CUT_TOLERANCE_NS
    w_end = last_op if cut else hi

    busy_total = 0.0
    per_op: dict[str, float] = {}
    gaps = []
    for ops in per_device:
        busy, self_ns = _busy_and_self(ops)
        busy_total += sum(end - start for start, end in busy)
        for name, ns in self_ns.items():
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + ns
        edge = lo
        for start, stop in busy + [[w_end, w_end]]:
            if start > edge:
                gaps.append((start - edge, edge, start))
            edge = max(edge, stop)

    ndev = len(devices)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[0])
    return TraceSummary(
        window_s=(w_end - lo) * 1e-9,
        busy_s=busy_total / ndev * 1e-9,
        devices=ndev,
        device_ops=[[name, ns / ndev * 1e-9] for name, ns in top],
        idle_gaps=[[_host_label(host_events, a, b), length * 1e-9]
                   for length, a, b in gaps[:TOP]],
        cut_s=(hi - w_end) * 1e-9,
    )
