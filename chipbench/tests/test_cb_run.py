"""Whole runs on the CPU at a small size, past the look for a chip: a sound
run is correct, a broken served path is not, and a host stall shows in the
wall-clock metrics."""

import time

import chipbench_fixtures as fx
import jax
import jax.numpy as jnp
import pytest

from harness.cell import run_cell
from harness.peaks import PEAKS
from harness.spec import Bench

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return Bench(fx.make_checkout(tmp_path_factory.mktemp("co")))


def _run(bench, cell, seconds=1.5, **kw):
    out = run_cell(bench, cell, SEED, seconds, False,
                   t_start=time.perf_counter(), peaks=PEAKS["TPU v5 lite"],
                   log=lambda *a: None, **kw)
    out.pop("record")
    return out


def test_a_sound_run_is_correct_and_reports_its_metrics(bench):
    out = _run(bench, "tiny.chat")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_s", "itl_p95_s", "setup_s"}
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert list(out["checks"]) == ["logit_gap", "compiles_in_window",
                                   "requests_failed"]


def _state_unchanged(engine, policy):
    """The decode step computes its token but returns the cache it got."""
    step = engine.decode_batch

    def broken(cache, tokens):
        kept = jax.tree.map(jnp.copy, cache)
        logits, _ = step(cache, tokens)
        return logits, kept
    engine.decode_batch = broken


def _token_altered(engine, policy):
    """Slot 0's token is the runner-up wherever the decode step makes it."""
    step = engine.decode_batch

    def broken(cache, tokens):
        logits, cache = step(cache, tokens)
        top = jnp.argmax(logits[0])
        return logits.at[0, top].set(-jnp.inf), cache
    engine.decode_batch = broken


@pytest.fixture(scope="module")
def bench_mean(tmp_path_factory):
    """The tiny cell held to the mean gap alone."""
    return Bench(fx.make_checkout(tmp_path_factory.mktemp("co"),
                                  {"mean_logit_gap": 1e-4}))


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
@pytest.mark.parametrize("held", ["logit_gap", "mean_logit_gap"])
def test_a_broken_served_path_is_not_correct(bench, bench_mean, held, fault):
    out = _run(bench if held == "logit_gap" else bench_mean, "tiny.chat",
               breaker=fault)
    assert not out["correct"]
    gap = out["checks"][held]
    assert gap["value"] > 10 * gap["limit"]


def _stall(secs=0.12):
    """A host stall after every scheduling event."""
    return lambda t: time.sleep(secs)


def test_a_host_stall_in_the_window_shows_in_the_open_mix(bench):
    base = _run(bench, "tiny.chat", seconds=2.0)["metrics"]
    slow = _run(bench, "tiny.chat", seconds=2.0, on_event=_stall())
    slow = slow["metrics"]
    assert slow["ttft_p50_s"]["value"] > base["ttft_p50_s"]["value"] + 0.05
    assert slow["itl_p95_s"]["value"] > base["itl_p95_s"]["value"] + 0.05


def test_a_request_left_unfinished_is_not_correct(tmp_path):
    """Serving stops at the window's close: requests still in their slots
    have failed, and the run is not correct however well the rest match."""
    b = Bench(fx.make_checkout(tmp_path, drain_cap_s=0.0))
    out = _run(b, "tiny.chat", on_event=_stall(0.05))
    assert out["failed"] > 0
    assert out["checks"]["requests_failed"]["value"] == out["failed"]
    assert out["checks"]["logit_gap"]["value"] <= 1e-3
    assert not out["correct"]


def test_the_programs_int8_path_in_the_float_programs_place_fails(tmp_path):
    """The control of a cell whose program has a lower-precision path of its
    own: that path, served on the cell's traffic and compared with the
    cell's reference, fails the mean gap's limit that the float path
    passes."""
    from harness import check
    from harness.cell import Served

    got = {}
    for name, cfg in (("float", fx.TINY), ("int8", fx.TINY_INT8)):
        b = Bench(fx.make_checkout(tmp_path / name, config=cfg,
                                   check_sample=64))
        served = Served(b, "tiny.chat", SEED, log=lambda *a: None)
        window, _, _ = served.serve(SEED, 4.0)
        finished = served.finished(window)
        served.close()
        got[name] = check.compare(fx.TINY, SEED, served.traffic, finished)
    limit = 1e-4                     # the tiny cell's, as in ``bench_mean``
    assert got["int8"]["tokens"] > 200
    assert got["float"]["mean_logit_gap"] <= limit < \
        got["int8"]["mean_logit_gap"]
