"""Trace reduction: busy union, idle share, per-program time, labelled gaps."""

import json
import pathlib

import chipbench_fixtures  # noqa: F401  (puts the harness on the path)
import pytest

from harness import trace as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, t0_ms, dur_ms):
    return tr.Ev(plane, line, name, t0_ms * 1e6, dur_ms * 1e6)


def synthetic():
    """A 100 ms window: a decode program (two overlapping ops), a gap while
    the host is inside its event, a prefill, and a gap between events."""
    return [
        _ev(HOST, "python", "cb.slice", 0, 100),
        _ev(HOST, "python", "cb.event", 0, 60),
        _ev(HOST, "python", "cb.decode", 1, 2),
        _ev(DEV, "XLA Modules", "jit_decode_fn(1)", 2, 20),
        _ev(DEV, "XLA Ops", "fusion.1", 2, 12),
        _ev(DEV, "XLA Ops", "fusion.2", 10, 12),     # overlaps fusion.1
        _ev(HOST, "python", "cb.prefill", 35, 1),
        _ev(DEV, "XLA Modules", "jit_prefill_fn(2)", 40, 15),
        _ev(DEV, "XLA Ops", "int8_matmul", 40, 15),
        _ev(DEV, "XLA Ops", "outside", 150, 10),     # after the window
    ]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    s = tr.summarize(synthetic())
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.020 + 0.015)
    assert s.module_times("decode_fn") == [pytest.approx(0.020)]
    assert s.module_times("prefill_fn") == [pytest.approx(0.015)]
    assert s.op_time("int8_matmul") == pytest.approx(0.015)
    assert s.op_time("missing") is None
    assert "outside" not in s.ops


def test_gaps_are_labelled_by_the_innermost_host_span():
    s = tr.summarize(synthetic())
    # gaps: 0-2 (cb.event, cb.decode covers 1-3: innermost), 22-40
    # (cb.event), 55-100 (no span after 60 -> between events at mid 77.5)
    got = {round(t * 1e3, 6): name for name, t in s.gaps}
    assert got == {45.0: "between events", 18.0: "cb.event",
                   2.0: "cb.decode"}
    assert [t for _, t in s.gaps] == sorted([t for _, t in s.gaps],
                                            reverse=True)
    b = tr.breakdown(s)
    assert b["idle_gaps"][0] == ["between events", pytest.approx(0.045)]
    assert b["device_ops"][0][0] in ("fusion.1", "fusion.2", "int8_matmul")


def test_ops_are_attributed_to_the_program_run_that_holds_them():
    s = tr.summarize(synthetic())
    assert s.op_in("prefill_fn", "int8_matmul") == (1, pytest.approx(0.015))
    assert s.op_in("decode_fn", "int8_matmul") == (0, 0)
    assert s.op_in("decode_fn", "fusion") == (2, pytest.approx(0.024))


def test_rows_round_trip():
    evs = synthetic()
    assert tr.from_rows(json.loads(json.dumps(tr.to_rows(evs)))) == evs


def test_a_trace_without_a_device_or_window_is_refused():
    with pytest.raises(RuntimeError):
        tr.summarize([e for e in synthetic() if e.plane == HOST])
    with pytest.raises(RuntimeError):
        tr.summarize([e for e in synthetic() if e.name != "cb.slice"])


def test_a_recorded_chip_trace():
    """Two scheduling events of ``minitron4b-int8.chat`` on one TPU v5e:
    a decode step each, then six one-token readbacks the host waits on."""
    path = pathlib.Path(__file__).parent / "data" / "trace_int8_chat.json"
    evs = tr.from_rows(json.loads(path.read_text())["events"])
    s = tr.summarize(evs)
    assert s.window_s == pytest.approx(0.0754)
    decode = s.module_times("decode_fn")
    assert len(decode) == 2 and all(0.026 < t < 0.027 for t in decode)
    assert len(s.module_times("dynamic_slice")) == 12     # the readbacks
    # every int8 layer matrix of 32 layers, twice
    assert s.op_time("%int8_matmul") == pytest.approx(0.0134, abs=1e-4)
    n, secs = s.op_in("decode_fn", "%int8_matmul")
    assert n == 2 * 6 * 32 and secs == pytest.approx(0.0134, abs=1e-4)
    assert s.busy_s == pytest.approx(0.05286, abs=1e-4)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.299, abs=1e-3)
    # the host is inside its scheduling event in every long gap: readbacks
    assert {name for name, _ in s.gaps} == {"cb.event"}


def test_the_int8_roofline_of_a_recorded_trace():
    """Two decode steps of 8 slots: the layer matrices' bytes over 819 GB/s,
    twice, over the kernel's 13.4 ms."""
    import json as js

    from harness import ops
    from harness.loop import Call
    from harness.peaks import PEAKS
    from harness.record import Run
    from harness.spec import load_reader

    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = js.loads((root / "configs" / "minitron4b-int8.json").read_text())
    path = pathlib.Path(__file__).parent / "data" / "trace_int8_chat.json"
    s = tr.summarize(tr.from_rows(js.loads(path.read_text())["events"]))
    calls = [Call("decode", 0.1, 0.2, 8), Call("decode", 0.3, 0.4, 8)]
    run = Run("minitron4b-int8.chat", cfg, {}, PEAKS["TPU v5 lite"],
              (0.0, 1.0), 1.0, {}, calls, 0.0, 0.0, trace=s,
              trace_window=(0.0, 1.0))
    got = load_reader(root / "metrics", "int8_matmul_roofline.chat").read(run)
    need = 2 * sum(ops.int8_matmul_bytes(*c) for c in ops.int8_calls(cfg, 8))
    assert got == pytest.approx(100 * need / 819e9 / s.op_time(
        "%int8_matmul"))
    assert 40 < got < 55
