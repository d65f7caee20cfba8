"""The trace reduction against numbers worked out by hand.

``data/small_tpu_trace.xplane.pb`` was recorded on one TPU v5 lite: inside a
``bench.window`` span, three rounds of a ``bench.fit`` span (a jitted
reduction of a 1024 x 1024 product and a jitted elementwise sine) and a
``bench.between_fits`` span (a 2 ms sleep). The first round's device
operations fall before the window on the trace's clock; the other eight
events are, in ns (start, duration):

    copy-start  48462943 13   copy-done  48462957 5927
    fusion      48468886 11818   sine_multiply_fusion 49383252 27904
    copy-start  52721948 14   copy-done  52721963 5903
    fusion      52727867 11820   sine_multiply_fusion 53326500 27906

and the window is [45303240, 58292241].
"""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench.trace_reduce import _busy_and_self, reduce, short_name

TRACE = Path(__file__).parent / "data" / "small_tpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return reduce(ProfileData.from_file(str(TRACE)))


def test_window_and_busy(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(12_989_001e-9)
    busy = 13 + 5927 + 11818 + 27904 + 14 + 5903 + 11820 + 27906
    assert summary.busy_s == pytest.approx(busy * 1e-9)


def test_top_device_ops(summary):
    ops = dict(summary.device_ops)
    assert list(ops)[:3] == ["%sine_multiply_fusion = f32[1024,1024] fusion",
                             "%fusion = f32[] fusion",
                             "%copy-done = f32[1024,1024] copy-done"]
    assert ops["%sine_multiply_fusion = f32[1024,1024] fusion"] == \
        pytest.approx((27904 + 27906) * 1e-9)
    assert ops["%fusion = f32[] fusion"] == pytest.approx(23638e-9)
    assert ops["%copy-done = f32[1024,1024] copy-done"] == \
        pytest.approx(11830e-9)
    assert ops["%copy-start = (...) copy-start"] == pytest.approx(27e-9)


def test_longest_idle_gaps(summary):
    want = [("bench.between_fits", 58292241 - 53354406),  # after the last op
            ("bench.fit", 52721948 - 49411156),
            ("bench.between_fits", 48462943 - 45303240),  # window start
            ("bench.between_fits", 49383252 - 48480704),
            ("bench.between_fits", 53326500 - 52739687)]
    got = summary.idle_gaps[:5]
    assert [g[0] for g in got] == [w[0] for w in want]
    assert [g[1] for g in got] == pytest.approx([w[1] * 1e-9 for w in want])


def test_nested_operations_count_once():
    # a while loop [0, 100) around two ops, then one op after a gap
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
              (120, 130, "a")]
    busy, self_ns = _busy_and_self(events)
    assert busy == [[0, 100], [120, 130]]
    assert self_ns == {"while": 30, "a": 30, "b": 50}


def test_short_name():
    hlo = ("%fusion.286 = f32[1024,1024]{1,0:T(8,128)S(1)} fusion("
           "f32[1024]{0:T(1024)S(1)} %gte.4998), kind=kOutput")
    assert short_name(hlo) == "%fusion.286 = f32[1024,1024] fusion"
    loop = ("%while.357 = (s32[]{:T(128)}, f32[12,1024,65]{1,0,2:T(8,128)})"
            " while((s32[]{:T(128)}) %tuple.595), condition=%c")
    assert short_name(loop) == "%while.357 = (...) while"


class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _profile(fit_end_ns):
    """A window [0, 1e9) whose one fit ends at ``fit_end_ns``; the device
    trace holds two operations, the last ending at 400 ms."""
    ms = 1_000_000
    host = _Plane("/host:CPU", [_Line("main", [
        _Ev("bench.window", 0, 1000 * ms), _Ev("bench.fit", 0, fit_end_ns)])])
    device = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev("%a = f32[] fusion", 0, 200 * ms),
        _Ev("%b = f32[] fusion", 210 * ms, 190 * ms)])])
    return type("Profile", (), {"planes": [host, device]})()


@pytest.mark.parametrize("fit_end_ms,window_s,cut_s,longest_gap_s", [
    (405, 1.0, 0.0, 0.6),  # the fit returned with its last operation
    (900, 0.4, 0.6, 0.01),  # the fit ran on past the trace's last operation
])
def test_a_device_trace_cut_short_ends_the_traced_window(
        fit_end_ms, window_s, cut_s, longest_gap_s):
    got = reduce(_profile(fit_end_ms * 1_000_000))
    assert got.window_s == pytest.approx(window_s)
    assert got.cut_s == pytest.approx(cut_s)
    assert got.busy_s == pytest.approx(0.39)
    assert got.idle_gaps[0][1] == pytest.approx(longest_gap_s)
