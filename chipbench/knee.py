#!/usr/bin/env python3
"""Find an open mix's knee on the chip: serve it at several fixed rates in one
process, in several arrival orders each, and report, per rate and order, the
share of requests that met both limits.

    python3 chipbench/knee.py --workload minitron4b.chat --seed 7 \
        --seconds 51 --rates 1,1.2,1.4 --orders 11,12,13

A request meets the limits (the mix's ``slo``) when its first token came
within ``ttft_s`` of when it was due and no gap between its tokens passed
``gap_s``; one that did not finish misses.  The knee is the highest rate at
which 90% meet them with no backlog left growing at the window's close, in
every order.  ``--seed`` makes the weights, each of ``--orders`` the traffic.
One JSON line per rate and order.
"""

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def attainment(stamps, window, slo):
    """(requests due, share meeting both limits, share meeting the TTFT
    limit, share meeting the gap limit, requests not yet admitted at the
    window's close)."""
    w0, w1 = window
    due = [s for s in stamps.values() if w0 <= s.due < w1]
    ttft = [s.done and s.tokens[0] - s.due <= slo["ttft_s"] for s in due]
    gap = [s.done and all(b - a <= slo["gap_s"] for a, b in
                          zip(s.tokens, s.tokens[1:])) for s in due]
    n = max(len(due), 1)
    waiting = sum(1 for s in due if s.prefill_start is None
                  or s.prefill_start > w1)
    return (len(due), sum(a and b for a, b in zip(ttft, gap)) / n,
            sum(ttft) / n, sum(gap) / n, waiting)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--orders", default=None)
    a = p.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU attached", file=sys.stderr)
        return 3
    from harness.cell import Served
    from harness.record import Run, percentile
    from harness.spec import Bench
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = Bench(HERE.parent)
    served = Served(bench, a.workload, a.seed,
                    log=lambda *x: print(*x, file=sys.stderr))
    orders = [int(o) for o in (a.orders or str(a.seed)).split(",")]
    for rate, order in [(float(r), o) for r in a.rates.split(",")
                        for o in orders]:
        traffic = dict(served.traffic, arrivals=dict(
            served.traffic["arrivals"], rate_per_s=rate))
        window, end, compiles = served.serve(order, a.seconds, traffic)
        d = served.driver
        run = Run(a.workload, served.cfg, traffic, {}, window, end,
                  dict(d.stamps), list(d.tap.calls), 0.0, 0.0)
        n, share, ttft, gap, waiting = attainment(run.stamps, window,
                                                  traffic["slo"])
        print(json.dumps({
            "rate_per_s": rate, "order": order, "requests": n,
            "met_share": share,
            "ttft_met_share": ttft, "gap_met_share": gap,
            "waiting_at_close": waiting, "drain_s": end - window[1],
            "failed": len(run.failed()),
            "ttft_p50_s": percentile(run.ttfts(), 50),
            "ttft_p90_s": percentile(run.ttfts(), 90),
            "itl_p50_s": percentile(run.gaps(), 50),
            "itl_p95_s": percentile(run.gaps(), 95),
            "compiles": compiles}), flush=True)
    served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
