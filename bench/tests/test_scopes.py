"""The phase reduction (bench/scopes.py) against numbers worked out by hand.

``_profile`` is a made-up trace in milliseconds: a window [0, 1000] with
three ``fit.chunk`` spans, A [10, 400] (8 steps), B [410, 700] (4 steps)
and C [710, 990] (8 steps), on one device whose trace ends at 800, so C is
not held. The window's first two ``jit_outer_scan`` executions pair with A
and B. The two programs (8 and 4 steps) share instruction names; a
``jit_init`` program has no scopes. The device's operations:

    module              op                  interval    own phase -> phase
    jit_init(333)       %fusion.0 f32[8]    [5, 15]     before A's run: out
    jit_outer_scan(111) %while.1            [20, 300]   solve; self 130
                        %fusion.1 f32[8,8]  [30, 130]   solve_mvm 100
                        %copy.3             [140, 160]  none -> solve 20
                        %fusion.2 f32[8]    [170, 200]  precond 30
                        %fusion.4 f32[8]    [300, 350]  grad (has gp.mvm) 50
                        %fusion.9 f32[8]    [350, 380]  adam 30
    jit_init(333)       %fusion.0 f32[8]    [420, 430]  not named 10
    jit_outer_scan(222) %fusion.9 f32[4]    [440, 640]  targets 200
                        %fusion.5 f32[4]    [640, 690]  gp.mvm alone: not named 50
    jit_outer_scan(111) %fusion.1 f32[8,8]  [720, 800]  C's run: out

So solve_mvm 0.10 s, solve 0.15, precond 0.03, grad 0.05, adam 0.03,
targets 0.20, not named 0.06; busy 0.36 + 0.26 = 0.62 s over 12 steps in
two chunks. The traced window ends at 800; its idle gaps [0, 5], [380,
420], [690, 720] fall outside any chunk (``bench.window``, 75 ms), [15,
20] and [430, 440] inside A and B (``fit.chunk``, 15 ms).

``data/small_scoped_trace.xplane.pb`` was recorded on one TPU v5 lite:
inside a ``bench.window`` span, two rounds of a ``fit.chunk`` span
(``steps=1``, opened with ``repro.obs.trace.span``) around an unscoped
jitted cosine and one call of ``jit_step``, a program with the six
``gp.*`` scopes whose compiled text is ``data/small_scoped_trace.hlo.txt``
(two solve iterations of an MVM and a preconditioner apply in a while
loop). The device's clock runs about 0.5 ms behind the host's here: the
first ``jit_step`` starts before the window does. Its operations, in ns
(start, duration), each run's start ``s`` at 44008717 and 47573371:

    %convert.2        s+272 373   s+273 374        (none)
    sine_multiply     s+647 3952  s+648 3952       targets
    %while            s+4600 24071  s+4600 24056   solve; self 22, 24
      %fusion.27      11527, 11730 | 11520, 11720  solve_mvm
      %fusion.28      396, 396 | 396, 396          precond
    %copy-start       6 | 6                        (none)
    %fusion.3         7961 | 7959                  grad
    %copy-done        2 | 3                        (none)
    %fusion.4         1382 | 1381                  (none: reduce_max root)
    cosine_add (jit__lambda) at 47079142, 4503     (none), between the runs

So solve_mvm 46497 ns, precond 1584, solve 46, targets 7904, grad 15920,
adam 0 (fused into the reduce_max fusion), not named 8030; busy 79981 ns
over 2 steps.
"""
from pathlib import Path

import pytest

from bench import scopes

DATA = Path(__file__).parent / "data"
MS = 1_000_000
PROG8 = """HloModule jit_outer_scan, is_scheduled=true

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b, metadata={op_name="jit(outer_scan)/while/body/gp.solve/while"}
  %fusion.1 = f32[8,8]{1,0} fusion(f32[8]{0} %p), kind=kOutput, calls=%f1, metadata={op_name="jit(outer_scan)/while/body/gp.solve/while/body/gp.mvm/dot_general"}
  %copy.3 = f32[8]{0} copy(f32[8]{0} %p)
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f2, metadata={op_name="jit(outer_scan)/while/body/gp.solve/gp.precond/dot_general"}
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f4, metadata={op_name="jit(outer_scan)/while/body/gp.grad/transpose(jvp(gp.mvm))/dot_general"}
  ROOT %fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f9, metadata={op_name="jit(outer_scan)/while/body/gp.adam/mul"}
}
"""
PROG4 = """HloModule jit_outer_scan, is_scheduled=true

ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %fusion.9 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%f9, metadata={op_name="jit(outer_scan)/while/body/gp.targets/sin"}
  ROOT %fusion.5 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%f5, metadata={op_name="jit(outer_scan)/while/body/gp.mvm/dot_general"}
}
"""
OPS = {
    "f0": "%fusion.0 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
    "w1": "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b",
    "f1": "%fusion.1 = f32[8,8]{1,0} fusion(f32[8]{0} %p), kind=kOutput",
    "c3": "%copy.3 = f32[8]{0} copy(f32[8]{0} %p)",
    "f2": "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
    "f4": "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
    "f9": "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
    "f9_4": "%fusion.9 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
    "f5_4": "%fusion.5 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
}


class _Ev:
    def __init__(self, name, start_ms, end_ms, stats=()):
        self.name = name
        self.start_ns = start_ms * MS
        self.duration_ns = (end_ms - start_ms) * MS
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _profile(chunk_span="fit.chunk"):
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.window", 0, 1000),
        _Ev(chunk_span, 10, 400, [("steps", 8)]),
        _Ev(chunk_span, 410, 700, [("steps", 4)]),
        _Ev(chunk_span, 710, 990, [("steps", 8)])])])
    modules = [_Ev("jit_init(333)", 5, 15), _Ev("jit_outer_scan(111)", 20, 390),
               _Ev("jit_init(333)", 420, 430),
               _Ev("jit_outer_scan(222)", 440, 690),
               _Ev("jit_outer_scan(111)", 720, 800)]
    ops = [_Ev(OPS["f0"], 5, 15), _Ev(OPS["w1"], 20, 300),
           _Ev(OPS["f1"], 30, 130), _Ev(OPS["c3"], 140, 160),
           _Ev(OPS["f2"], 170, 200), _Ev(OPS["f4"], 300, 350),
           _Ev(OPS["f9"], 350, 380), _Ev(OPS["f0"], 420, 430),
           _Ev(OPS["f9_4"], 440, 640), _Ev(OPS["f5_4"], 640, 690),
           _Ev(OPS["f1"], 720, 800)]
    device = _Plane("/device:TPU:0", [_Line("XLA Modules", modules),
                                      _Line("XLA Ops", ops)])
    return type("Profile", (), {"planes": [host, device]})()


@pytest.mark.parametrize("op_name,phase", [
    ("jit(f)/while/body/gp.solve/gp.precond/gp.mvm/dot", "precond"),
    ("jit(f)/gp.grad/transpose(jvp(gp.solve/gp.mvm))/dot", "grad"),
    ("jit(f)/gp.solve/while/body/gp.mvm/dot_general", "solve_mvm"),
    ("jit(f)/gp.solve/while/body/mul", "solve"),
    ("jit(f)/gp.targets/sin", "targets"),
    ("jit(f)/gp.adam/mul;gp.targets/add", "targets"),
    ("jit(f)/gp.mvm/dot", None),
    ("jit(f)/gp.solver/dot", None),
    ("jit(f)/while/body/closed_call", None),
])
def test_phase_precedence(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def test_phases_by_hand():
    got = scopes.reduce_phases(_profile(), [PROG4, PROG8])
    assert got.chunks == 2 and got.steps == 12
    want = {"solve_mvm": 0.10, "solve": 0.15, "precond": 0.03, "grad": 0.05,
            "adam": 0.03, "targets": 0.20, None: 0.06}
    assert got.seconds == pytest.approx(want)
    assert got.busy_s == pytest.approx(0.62)
    assert got.named_share() == pytest.approx(0.56 / 0.62)
    assert got.idle_s == pytest.approx({"bench.window": 0.075,
                                        "fit.chunk": 0.015})


def _recorded():
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(DATA / "small_scoped_trace.xplane.pb"))
    return profile, (DATA / "small_scoped_trace.hlo.txt").read_text()


def _assert_recorded_phases(got):
    assert got.chunks == 2 and got.steps == 2
    want = {"solve_mvm": 46497, "precond": 1584, "solve": 46,
            "targets": 7904, "grad": 15920, "adam": 0, None: 8030}
    assert got.seconds == pytest.approx({k: v * 1e-9
                                         for k, v in want.items()})
    assert got.busy_s == pytest.approx(79981e-9)


def test_phases_of_a_trace_recorded_on_the_chip():
    profile, text = _recorded()
    _assert_recorded_phases(scopes.reduce_phases(profile, [text]))


@pytest.mark.parametrize("chunk_span,programs", [
    ("bench.fit", [PROG4, PROG8]),  # a driver without fit.chunk spans
    ("fit.chunk", [PROG4.replace("gp.", "xx."), PROG8.replace("gp.", "xx.")]),
])
def test_nothing_to_read_without_spans_or_scopes(chunk_span, programs):
    assert scopes.reduce_phases(_profile(chunk_span), programs) is None


class _Compiled:
    """Stands for a lowered program: compiling it gives the recorded text."""

    def __init__(self, text):
        self.text = text

    def compile(self):
        return self

    def as_text(self):
        return self.text


def test_window_phases_reads_the_profile_kept_in_ctx():
    from types import SimpleNamespace

    profile, text = _recorded()
    stub = SimpleNamespace(window_programs=lambda cell: [_Compiled(text)])
    ctx = {"cell": SimpleNamespace(generator=stub), "profile": profile}
    got = scopes.window_phases(ctx)
    _assert_recorded_phases(got)
    assert scopes.window_phases(ctx) is got  # read once for all readers
    # An untraced run keeps no profile: nothing to read.
    assert scopes.window_phases({"cell": ctx["cell"], "profile": None}) is None


def test_the_generators_programs_carry_the_scopes(tiny_root):
    from bench.spec import load_cell

    cell = load_cell("tiny.fit_budget", root=tiny_root)
    with scopes._fresh_compiles():
        texts = [p.compile().as_text()
                 for p in cell.generator.window_programs(cell)]
    progs = [scopes.Program.parse(t) for t in texts]
    assert len(progs) == len(cell.generator.chunk_lengths(
        cell.config["outer_steps"]))
    assert all(p.module == "jit_outer_scan" for p in progs)
    for prog in progs:
        phases = {scopes.phase_of(n) for n in prog.op_names.values()}
        assert {"solve_mvm", "precond", "grad"} <= phases
