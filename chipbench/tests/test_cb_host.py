"""The served path's own host spans in a trace: gaps named by them, idle
time inside them, the readback and occupancy readers."""

import json
import pathlib

import chipbench_fixtures  # noqa: F401  (puts the harness on the path)
import jax
import pytest

from harness import host, trace as tr
from harness.loop import Call
from harness.record import Run
from harness.spec import load_reader

DEV, HOST = "/device:TPU:0", "/host:CPU"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
DATA = pathlib.Path(__file__).parent / "data"


def _ev(plane, line, name, t0_ms, dur_ms):
    return tr.Ev(plane, line, name, t0_ms * 1e6, dur_ms * 1e6)


def synthetic():
    """A 100 ms window: one scheduling event (0-60) whose policy step
    (1-58) decodes, reads two tokens back, then admits a request; the
    driver between events after it."""
    h = lambda name, t0, dur: _ev(HOST, "python3", name, t0, dur)  # noqa
    return [
        h("cb.slice", 0, 100),
        h("cb.event", 0, 60),
        h("serve.step", 1, 57),
        h("serve.decode", 1, 2),
        h("cb.decode", 1, 1),
        _ev(DEV, "XLA Modules", "jit_decode_fn(1)", 2, 20),
        _ev(DEV, "XLA Ops", "fusion.1", 2, 12),
        _ev(DEV, "XLA Ops", "fusion.2", 10, 12),
        h("serve.decode.wait", 3, 19),
        h("serve.bill", 22, 1),
        h("serve.readback", 23, 12),
        h("serve.d2h", 23, 5),
        _ev(DEV, "XLA Modules", "jit_dynamic_slice(2)", 26, 1),
        _ev(DEV, "XLA Ops", "dynamic-slice.1", 26, 1),
        h("serve.d2h", 29, 6),
        h("serve.admit", 35, 23),
        h("serve.prefill", 36, 2),
        h("serve.prefill.wait", 38, 17),
        _ev(DEV, "XLA Modules", "jit_prefill_fn(3)", 40, 15),
        _ev(DEV, "XLA Ops", "fusion.3", 40, 15),
        h("serve.insert", 55, 2),
    ]


def test_gaps_are_named_by_the_programs_span_inside_the_event():
    evs = synthetic()
    got = {round(t * 1e3, 6): name for name, t in host.labelled_gaps(evs)}
    # idle: 0-2 (cb.decode is innermost at 1), 22-26 and 27-40 (inside a
    # token read), 55-100 (after the event: between events)
    assert got == {2.0: "cb.decode", 4.0: "serve.d2h", 13.0: "serve.d2h",
                   45.0: "between events"}
    # the benchmark's own reduction, which sees only cb.* spans, is as it was
    old = {round(t * 1e3, 6): name for name, t in tr.summarize(evs).gaps}
    assert old == {2.0: "cb.decode", 4.0: "cb.event", 13.0: "cb.event",
                   45.0: "between events"}


def test_idle_inside_the_program_is_idle_under_its_spans():
    evs = synthetic()
    # serve.* covers 1-58: idle 1-2, 22-26, 27-40, 55-58 of 100 ms
    assert host.idle_in_program(evs) == pytest.approx(0.21)
    s = tr.summarize(evs)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.64)
    assert host.idle_in_program([e for e in evs if not
                                 e.name.startswith("serve.")]) is None


def test_overlap_of_interval_lists():
    assert host.overlap([(0, 2), (5, 9)], [(1, 6), (8, 20)]) == 1 + 1 + 1
    assert host.overlap([], [(0, 1)]) == 0


def _run(calls=(), traced=True, evs=None):
    summary = tr.summarize(evs or synthetic()) if traced else None
    return Run("minitron4b.chat", {}, {}, {}, (0.0, 1.0), 1.0, {},
               list(calls), 0.0, 0.0, trace=summary,
               trace_window=(0.0, 1.0) if traced else None)


@pytest.fixture
def from_rows(monkeypatch):
    """The readers read the given events as their run's trace."""
    def use(evs):
        monkeypatch.setattr(host, "events", lambda run: evs)
    return use


def test_readback_and_idle_readers_on_synthetic_rows(from_rows):
    from_rows(synthetic())
    run = _run()
    rb = load_reader(METRICS, "readback_ms_per_step.chat").read(run)
    assert rb == pytest.approx(12.0)
    share = load_reader(METRICS, "idle_in_program_share.chat").read(run)
    assert share == pytest.approx(21.0)


def test_readers_find_nothing_in_a_program_without_spans(from_rows):
    """The benchmark's files over a program that writes no ``serve.*``
    span (the recorded int8 trace): nothing to read, and no error."""
    rows = json.loads((DATA / "trace_int8_chat.json").read_text())["events"]
    from_rows(tr.from_rows(rows))
    run = _run()
    for name in ("readback_ms_per_step.chat", "idle_in_program_share.chat"):
        assert load_reader(METRICS, name).read(run) is None, name
    from_rows(None)
    for name in ("readback_ms_per_step.chat", "idle_in_program_share.chat"):
        assert load_reader(METRICS, name).read(run) is None, name


def test_batch_occupancy_over_the_runs_decode_calls():
    calls = [Call("prefill", 0.0, 0.1, 256),
             Call("decode", 0.1, 0.2, 8, (300, 40)),
             Call("decode", 0.2, 0.3, 8, (301, 41, 256, 17)),
             Call("decode", 0.3, 0.4, 8, (302,))]
    read = load_reader(METRICS, "batch_occupancy.chat").read
    assert read(_run(calls)) == pytest.approx(100 * 7 / 24)
    assert read(_run(calls[:1])) is None


def test_events_come_from_this_runs_trace_only(tmp_path):
    """``host.events`` reads the directory a traced run wrote, and refuses
    a trace whose window is not the run's."""
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.SLICE_SPAN):
        with jax.profiler.TraceAnnotation("serve.readback", live=1):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    evs = tr.load(str(tmp_path))
    w0, w1 = tr.window(evs)
    run = _run()
    run.trace.window_s = (w1 - w0) / 1e9
    got = host.events(run, tmp_path)
    assert [e.name for e in host.within(got, "serve.readback")] == \
        ["serve.readback"]
    run.trace.window_s += 1e-6
    assert host.events(run, tmp_path) is None
    assert host.events(_run(traced=False), tmp_path) is None
    assert host.events(run, tmp_path / "missing") is None


def test_a_recorded_chip_trace_with_the_programs_spans(from_rows):
    """Two scheduling events of ``minitron4b.chat`` on one TPU v5e, the
    first admitting a request: the idle gaps sit inside the program's
    spans, above all the slot insertion and the token reads."""
    rows = json.loads((DATA / "trace_bf16_chat_spans.json").read_text())
    evs = tr.from_rows(rows["events"])
    s = tr.summarize(evs)
    assert s.window_s == pytest.approx(0.084955)
    step = pytest.approx(0.024233, abs=1e-6)
    assert s.module_times("decode_fn") == [step, step]
    assert s.module_times("prefill_fn") == [
        pytest.approx(0.011850, abs=1e-6)]
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.16997, abs=1e-5)
    # the benchmark's own reduction names the event alone, as before
    assert {name for name, _ in s.gaps} == {"cb.event"}
    named = host.labelled_gaps(evs)
    assert [t for _, t in named] == [t for _, t in s.gaps]
    assert named[0] == ("serve.insert", pytest.approx(0.002032, abs=1e-6))
    assert {name for name, _ in named} == {
        "serve.insert", "serve.d2h", "serve.first_token",
        "serve.decode.wait"}
    # two decode steps, two live slots each: one read per slot per step
    assert len(host.within(evs, "serve.d2h")) == 4
    from_rows(evs)
    run = _run(evs=evs)
    rb = load_reader(METRICS, "readback_ms_per_step.chat").read(run)
    assert rb == pytest.approx((2.889980 + 2.595290) / 2)
    share = load_reader(METRICS, "idle_in_program_share.chat").read(run)
    assert share == pytest.approx(16.9123, abs=1e-3)
