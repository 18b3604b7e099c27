"""Traffic generation and the tail arithmetic, on the CPU."""

import math

import chipbench_fixtures as fx
import numpy as np
import pytest

from harness import traffic as tf
from harness.loop import Stamp
from harness.record import Run, percentile

SEED = 2**33 + 17          # over 32 bits: every bit must count


def _sig(jobs):
    return [(j.rid, round(j.due_s, 12), j.max_new, j.prompt.tobytes())
            for j in jobs]


def test_open_loop_is_fixed_by_the_seed():
    a = tf.open_loop(fx.CHAT, SEED, 10.0, 1024)
    b = tf.open_loop(fx.CHAT, SEED, 10.0, 1024)
    c = tf.open_loop(fx.CHAT, SEED + 2**32, 10.0, 1024)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)
    # another seed, the same work in another order
    assert sorted(len(j.prompt) for j in a) == sorted(len(j.prompt) for j in c)
    assert sorted(j.max_new for j in a) == sorted(j.max_new for j in c)
    ga = sorted(np.diff([j.due_s for j in a]).round(9))
    gc = sorted(np.diff([j.due_s for j in c]).round(9))
    assert len(ga) == len(gc)


def test_open_loop_fills_the_window_at_the_rate():
    jobs = tf.open_loop(fx.CHAT, 5, 10.0, 1024)
    assert len(jobs) == round(fx.CHAT["arrivals"]["rate_per_s"] * 10.0)
    due = [j.due_s for j in jobs]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 10.0
    assert all(0 <= t < 1024 for j in jobs for t in j.prompt)


@pytest.mark.parametrize("x,want", [(64, 64), (90, 64), (91, 128),
                                    (181, 128), (182, 256), (700, 512),
                                    (800, 1024), (5000, 1024), (10, 64)])
def test_prompts_round_to_the_nearest_bucket_in_log_space(x, want):
    assert tf.round_log(x, tf.pow2_buckets(64, 1024)) == want


def test_lognormal_lengths_land_on_buckets_with_the_stated_median():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64,
         "max": 1024, "round": "pow2_log"}
    x = tf.stratified(d, 1001)
    assert set(x) <= {64, 128, 256, 512, 1024}
    assert np.median(x) == 256
    y = tf.stratified(dict(d, round=None), 1001)
    assert y.min() >= 64 and y.max() <= 1024
    assert math.isclose(np.median(y), 256, abs_tol=1)


def _run(stamps, window=(0.0, 10.0), end=12.0):
    return Run("t", {}, {}, {}, window, end, {s.rid: s for s in stamps}, [],
               0.0, 0.0)


def test_tails_are_taken_over_every_request_and_token():
    # 95 quick requests and 5 slow ones: a p90 over chunk medians would
    # never see the slow ones
    stamps = [Stamp(i, 0.1 * i, 8, 3, tokens=[0.1 * i + 0.01,
                                              0.1 * i + 0.02,
                                              0.1 * i + 0.03])
              for i in range(95)]
    stamps += [Stamp(95 + i, 9.5 + 0.05 * i, 8, 3,
                     tokens=[9.5 + 0.05 * i + 2.0, 12.0, 12.5])
               for i in range(5)]
    run = _run(stamps)
    ttft = run.ttfts()
    assert len(ttft) == 100
    assert percentile(ttft, 90) == pytest.approx(np.percentile(ttft, 90))
    assert percentile(ttft, 96) > 1.0
    gaps = run.gaps()
    assert len(gaps) == 200
    assert max(gaps) == pytest.approx(0.5)
    assert percentile(gaps, 99) > 0.4


def test_a_request_with_no_token_counts_until_serving_stopped():
    run = _run([Stamp(0, 1.0, 8, 3), Stamp(1, 2.0, 8, 3, tokens=[2.5])],
               end=20.0)
    assert sorted(run.ttfts()) == [0.5, 19.0]
    assert len(run.failed()) == 2


def test_only_requests_due_in_the_window_are_judged():
    run = _run([Stamp(0, -0.5, 8, 2, tokens=[0.1, 0.2]),
                Stamp(1, 5.0, 8, 2, tokens=[5.1, 5.3]),
                Stamp(2, 10.0, 8, 2)])
    assert [s.rid for s in run.due_in_window()] == [1]
    assert run.ttfts() == [pytest.approx(0.1)]
    assert run.gaps() == [pytest.approx(0.2)]
    assert run.failed() == []
