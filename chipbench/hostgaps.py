#!/usr/bin/env python3
"""Put a traced run's device idle time down to the program's own host spans.

    python3 chipbench/hostgaps.py --workload minitron4b.chat --seed 7 \
        --seconds 51 [--fixture <rows>.json]

One traced run of the cell, as ``run.py --trace 1`` makes it (its result
line is printed first), then from the same trace: the ten longest idle gaps
named by the innermost ``cb.*`` or ``serve.*`` span, each span's count,
total and self time in the traced slice, the mean scheduling event and the
spans in it, where the time of each token readback (``serve.d2h``) goes,
the deploy's registry round trip, the core's counters, the engine's
compiled shapes, and what one host span costs with the profiler off and
on.  One JSON line, also written under ``.chipbench/hostgaps/``.  With
``--fixture`` it also writes two scheduling events of the slice, the first
with an admission, as rows for the tests.  Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
OUT = HERE.parent / ".chipbench" / "hostgaps"


def span_table(evs, w0, w1):
    """name -> [count, total ms, self ms] of the host spans (``cb.*`` but
    the slice, ``serve.*``) wholly inside the window; children are the
    spans nested inside a span on its line."""
    from harness import host

    sp = [e for e in host.spans(evs, "cb.") + host.spans(evs)
          if e.name != "cb.slice" and w0 <= e.t0 and e.t1 <= w1]
    sp.sort(key=lambda e: (e.plane, e.line, e.t0, -e.dur))
    child = {id(e): 0.0 for e in sp}
    stack = []
    for e in sp:
        while stack and (stack[-1].line != e.line or stack[-1].t1 < e.t1):
            stack.pop()
        if stack:
            child[id(stack[-1])] += e.dur
        stack.append(e)
    out = {}
    for e in sp:
        row = out.setdefault(e.name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += e.dur / 1e6
        row[2] += (e.dur - child[id(e)]) / 1e6
    return dict(sorted(out.items(), key=lambda kv: -kv[1][2]))


def readbacks(evs, w0, w1):
    """Medians over the ``serve.d2h`` spans in the window: host time before
    the first device program the read starts, device time, host time after
    the last ends (ms); and the host runtime's own events inside them."""
    from harness import trace as tr

    d2h = [e for e in evs if e.name == "serve.d2h" and w0 <= e.t0
           and e.t1 <= w1]
    runs = sorted((e.t0, e.t1) for e in evs if tr.is_device(e)
                  and e.line == tr.MODULES_LINE)
    before, device, after = [], [], []
    for s in d2h:
        inside = [r for r in runs if s.t0 <= r[0] < s.t1]
        if inside:
            before.append((inside[0][0] - s.t0) / 1e6)
            device.append((inside[-1][1] - inside[0][0]) / 1e6)
            after.append((s.t1 - inside[-1][1]) / 1e6)
    d2h.sort(key=lambda s: s.t0)
    starts = [s.t0 for s in d2h]
    runtime = {}
    for e in evs:
        if tr.is_device(e) or e.name.startswith(("cb.", "serve.")):
            continue
        i = bisect.bisect_right(starts, e.t0) - 1
        if i >= 0 and e.t1 <= d2h[i].t1:
            runtime[e.name] = runtime.get(e.name, 0.0) + e.dur / 1e6
    med = (lambda xs: statistics.median(xs) if xs else None)  # noqa: E731
    return {"count": len(d2h), "with_device_run": len(before),
            "median_ms": med([s.dur / 1e6 for s in d2h]),
            "before_device_ms": med(before), "device_ms": med(device),
            "after_device_ms": med(after),
            "host_runtime_ms": dict(sorted(runtime.items(),
                                           key=lambda kv: -kv[1])[:16])}


def gap_starts(evs, w0, top=10):
    """Where the ``top`` longest idle gaps start, in ms from the window's
    start, longest first."""
    from harness import host

    gaps = sorted((g for gs in host.idle(evs).values() for g in gs),
                  key=lambda g: g[0] - g[1])[:top]
    return [(g[0] - w0) / 1e6 for g in gaps]


def events_table(evs, w0, w1):
    """The scheduling events wholly inside the window: count, mean ms, and
    ``serve.*`` spans per event."""
    from harness import host

    ev = [e for e in evs if e.name == "cb.event" and w0 <= e.t0
          and e.t1 <= w1]
    serve = sorted(e.t0 for e in host.spans(evs))
    n_in = sum(1 for t in serve if any(e.t0 <= t <= e.t1 for e in ev))
    return {"count": len(ev),
            "mean_ms": statistics.mean(e.dur for e in ev) / 1e6 if ev
            else None,
            "serve_spans_per_event": n_in / len(ev) if ev else None}


def annotation_cost(n=20000):
    """Microseconds per ``serve.*`` span with two args, profiler off, then
    on."""
    import jax

    def per_span():
        t0 = time.perf_counter()
        for i in range(n):
            with jax.profiler.TraceAnnotation("serve.cost", rid=i, slot=3):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    off = per_span()
    d = tempfile.mkdtemp(prefix="hostgaps-cost-")
    try:
        jax.profiler.start_trace(d)
        on = per_span()
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"off_us": off, "on_us": on}


def fixture(evs, w0, w1, workload):
    """Two scheduling events, the first holding an admission: every device
    event and every ``cb.*``/``serve.*`` span in them, times in ns from the
    first event's start, op names cut to the HLO instruction name."""
    from harness import host, trace as tr

    ev = sorted((e for e in evs if e.name == "cb.event" and w0 <= e.t0
                 and e.t1 <= w1), key=lambda e: e.t0)
    prefills = [e.t0 for e in evs if e.name == "serve.prefill"]
    for a, b in zip(ev, ev[1:]):
        if any(a.t0 <= t <= a.t1 for t in prefills) and \
                not any(b.t0 <= t <= b.t1 for t in prefills):
            break
    else:
        return None
    t0, t1 = a.t0, b.t1
    keep = [e for e in evs if e.t1 > t0 and e.t0 < t1 and (
        tr.is_device(e) or (e.name.startswith(("cb.", "serve."))
                            and e.name != "cb.slice"))]
    rows = [[e.plane, e.line, tr.op_name(e.name) if e.line == tr.OPS_LINE
             else e.name, e.t0 - t0, e.dur] for e in keep]
    host_line = next(e for e in host.spans(evs, "cb.") if e.name == "cb.slice")
    rows.insert(0, [host_line.plane, host_line.line, "cb.slice", 0.0,
                    t1 - t0])
    return {"about": f"Two scheduling events of {workload} traced on one "
                     "TPU v5e, the first with an admission (prefill, slot "
                     "insert) and both with a decode step, with the served "
                     "path's serve.* spans; times in ns from the first "
                     "event's start; op names cut to the HLO instruction "
                     "name; the cb.slice span cut to these two events",
            "events": rows}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fixture", default=None)
    a = p.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("hostgaps: no TPU attached", file=sys.stderr)
        return 3
    from harness import cell, host, trace as tr
    from harness.peaks import peaks
    from harness.spec import Bench
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seen = {}
    close = cell.Served.close

    def keep_then_close(self):
        # the program's own records, before the run frees its state
        seen["deploy_phases_s"] = getattr(self.session, "deploy_phases_s",
                                          None)
        seen["counters"] = getattr(self.core, "counters", None)
        compiled = getattr(self.core.engine, "_compiled", {})
        seen["compiled"] = {f"{e}{list(shape)}": n
                            for (e, shape), n in compiled.items()}
        close(self)

    cell.Served.close = keep_then_close
    shutil.rmtree(host.TRACE_DIR, ignore_errors=True)
    log = lambda *x: print(*x, file=sys.stderr, flush=True)  # noqa: E731
    out = cell.run_cell(Bench(HERE.parent), a.workload, a.seed, a.seconds,
                        True, t_start=T_START,
                        peaks=peaks(jax.devices()[0].device_kind),
                        trace_dir=str(host.TRACE_DIR), log=log)
    record = out.pop("record")
    out.pop("checks")
    print(json.dumps(out), flush=True)
    evs = tr.load(str(host.TRACE_DIR))
    shutil.rmtree(host.TRACE_DIR, ignore_errors=True)
    w0, w1 = tr.window(evs)
    line = {"workload": a.workload, "seed": a.seed,
            "correct": out["correct"], "deploy_s": record.deploy_s, **seen,
            "window_s": (w1 - w0) / 1e9,
            "device_idle_share": 1 - record.trace.busy_s /
            record.trace.window_s,
            "idle_in_program_share": host.idle_in_program(evs),
            "gaps": host.labelled_gaps(evs),
            "gap_starts_ms": gap_starts(evs, w0),
            "events": events_table(evs, w0, w1),
            "readbacks": readbacks(evs, w0, w1),
            "spans": span_table(evs, w0, w1),
            "annotation": annotation_cost()}
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{a.workload}.{a.seed}.json").write_text(json.dumps(line))
    if a.fixture:
        fx = fixture(evs, w0, w1, a.workload)
        if fx is None:
            log("hostgaps: no admission followed by a plain decode event")
        else:
            pathlib.Path(a.fixture).write_text(json.dumps(
                fx, separators=(",", ":")))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
