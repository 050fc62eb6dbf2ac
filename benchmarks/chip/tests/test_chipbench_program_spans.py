"""The program's spans: read from made-up traces with known answers, and
from a real profiler trace of a small fleet on the CPU."""

import tempfile
import time
from types import SimpleNamespace

import chipbench_testlib as TL  # noqa: F401
import pytest

from harness import spans as S
from harness import spec
from harness import trace as T

E = T.Event


def P(name, start, end, line):
    return S.Span("vsr:" + name, start, end, line)


def made_up():
    """Window [0.5, 4.0) s, two devices, program spans on three lines:
    a step on line 0 and one on line 1 wholly inside the window, a step
    on line 0 that runs past it, router stages on line 2."""
    ops = {0: [E("fusion.1", 0.5, 1.1), E("fusion.2", 1.25, 1.65),
               E("fusion.3", 2.7, 2.9)],
           1: [E("fusion.1", 0.5, 0.8)]}
    host = [E("bench:window", 0.5, 4.0), E("bench:lane.step:a", 1.0, 2.0)]
    program = [
        P("sched.step", 1.0, 2.0, 0),
        P("decode.launch", 1.1, 1.2, 0), P("decode.fetch", 1.2, 1.7, 0),
        P("decode.emit", 1.7, 1.9, 0),
        P("sched.step", 2.5, 3.5, 1),
        P("prefill.fetch", 2.6, 2.8, 1), P("spec.draft", 2.9, 3.2, 1),
        P("spec.draft.fetch", 3.0, 3.1, 1),
        P("decode.fetch", 1.3, 1.4, 1),     # another line: not step 0's
        P("sched.step", 3.9, 4.2, 0),
        P("stage:dispatch", 2.0, 2.6, 2), P("stage:decide", 3.4, 3.5, 2)]
    return T.Trace(ops, {}, host, (0.5, 4.0)), program


def test_step_self_time_leaves_out_its_own_threads_fetches():
    tr, program = made_up()
    # step 0: 1.0 s less its 0.5 s fetch; step 1: 1.0 s less 0.2 + 0.1 s;
    # the step past the window's end is left out
    assert sorted(S.step_self_ms(program, tr.window)) == \
        [pytest.approx(500.0), pytest.approx(700.0)]


def test_idle_outside_step_share():
    tr, program = made_up()
    # device 0: ops and steps cover [0.5, 2.0) + [2.5, 3.5) + [3.9, 4.0)
    # = 2.6 s of 3.5; device 1 covers [0.5, 0.8) + the same steps = 2.4
    want = ((3.5 - 2.6) + (3.5 - 2.4)) / 2 / 3.5
    assert S.idle_outside_step_share(tr, program) == pytest.approx(want)


def test_gaps_are_named_by_the_stepping_threads_phase():
    tr, program = made_up()
    assert S.idle_by_phase(tr, program) == [
        # mid 3.45: step 1 is open on line 1; the narrower stage span
        # open on line 2 belongs to a thread that is not stepping
        ["vsr:sched.step", pytest.approx(1.1)],
        # mid 2.175: no step open anywhere
        ["idle: no lane step (vsr:stage:dispatch)", pytest.approx(1.05)],
        ["vsr:decode.launch", pytest.approx(0.15)]]
    assert S.gap_name(program, 0.6) == "idle: no lane step (no program span)"


def test_idle_time_without_program_spans_has_no_phase():
    tr, _ = made_up()
    # the same gaps as the harness's own naming, none inside a lane step
    assert S.idle_by_phase(tr, []) == [
        ["idle: no lane step (no program span)",
         pytest.approx(sum(g[1] for g in T.idle_gaps(tr)))]]


def _slice(monkeypatch, spans):
    sl = S.Slice(spans, (0.5, 4.0))
    monkeypatch.setattr(S, "of", lambda ctx: sl)


@pytest.mark.parametrize("name,want", [
    ("host.step_self_ms_p50", 600.0),
    ("device.idle_outside_step_share", 100 * 2.0 / 2 / 3.5),
])
def test_profiler_trace_readers(name, want, monkeypatch, capsys):
    tr, program = made_up()
    reader = spec.load_reader(name)
    _slice(monkeypatch, program)
    assert reader.read(SimpleNamespace(trace=tr)) == pytest.approx(want)
    if name == "device.idle_outside_step_share":
        assert "vsr:decode.launch" in capsys.readouterr().err
    # the parent program opens no spans: nothing to read
    _slice(monkeypatch, [])
    assert reader.read(SimpleNamespace(trace=tr)) is None
    monkeypatch.setattr(S, "of", lambda ctx: None)
    assert reader.read(SimpleNamespace(trace=None)) is None


def test_nothing_to_read_without_operations_or_steps():
    tr = T.Trace(window=(0.0, 1.0))
    assert S.idle_by_phase(tr, []) == []
    assert S.idle_outside_step_share(tr, []) is None
    assert S.step_self_ms([P("decode.fetch", 0.1, 0.2, 0)], tr.window) == []


def test_a_reader_finds_the_newest_runs_trace(tmp_path, monkeypatch):
    """``spans.of`` reads the file in the newest ``chipbench-trace-*``
    directory, whatever its window, and nothing in a run with no trace."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from repro.core.observability import trace_span
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    traced = SimpleNamespace(trace=T.Trace())
    assert S.of(traced) is None                     # no slice taken yet
    for name in ("old", "new"):
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tdir)
        try:
            with TraceAnnotation(T.WINDOW_SPAN), trace_span(name):
                jnp.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        time.sleep(0.05)
    sl = S.of(traced)
    assert [s.name for s in sl.spans] == ["vsr:new"]
    assert sl.window[0] <= sl.spans[0].start <= sl.spans[0].end \
        <= sl.window[1]
    assert S.of(SimpleNamespace(trace=None)) is None


def test_a_fleet_under_the_profiler_writes_nested_lane_phases(tmp_path):
    """A small fleet generates under ``jax.profiler``; the trace holds
    lane steps with the prefill and decode phases inside them on the same
    thread."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.serving.fleet import LocalFleet
    fleet = LocalFleet(["smollm-360m"], reduced=True, batch=2,
                       gen_tokens=3)
    tdir = str(tmp_path)
    jax.profiler.start_trace(tdir)
    try:
        with TraceAnnotation(T.WINDOW_SPAN):
            fleet.generate("smollm-360m", ["one two three", "four five"])
    finally:
        jax.profiler.stop_trace()
    sl = S.load(T.find_xplane(tdir))
    program, window = sl.spans, sl.window
    steps = [s for s in program if s.name == S.STEP]
    assert steps
    for phase in ("decode.launch", "decode.fetch", "decode.emit",
                  "prefill.admit", "prefill.launch", "prefill.fetch"):
        inner = [s for s in program if s.name == "vsr:" + phase]
        assert inner, phase
        for s in inner:
            assert any(st.line == s.line and st.start <= s.start
                       and s.end <= st.end for st in steps), phase
    launch = min((s for s in program if s.name == "vsr:decode.launch"),
                 key=lambda s: s.start)
    fetch = min((s for s in program if s.name == "vsr:decode.fetch"
                 and s.start >= launch.end), key=lambda s: s.start)
    emit = min((s for s in program if s.name == "vsr:decode.emit"
                and s.start >= fetch.end), key=lambda s: s.start)
    assert launch.line == fetch.line == emit.line
    assert S.step_self_ms(program, window)
