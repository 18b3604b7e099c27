"""The served path's own host spans (``serve.*``) in a traced run.

The program writes them on the profiler's host plane, on the device's clock
(``repro.serving.telemetry.host``).  A run's record keeps only the trace's
summary, so the readers of these spans read the trace's events again from
the directory that ``run.py`` has the profiler write to, while the run still
holds it.  A program without such spans gives them nothing to read.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace as tr
from harness.spec import ROOT

PREFIX = "serve."
# where ``run.py`` has the profiler write a traced run
TRACE_DIR = ROOT / ".chipbench" / "trace"


def events(run, trace_dir=TRACE_DIR) -> Optional[List[tr.Ev]]:
    """The events of ``run``'s trace; None where the run was not traced or
    the directory holds no trace of this run's window."""
    if run.trace is None or not trace_dir.is_dir():
        return None
    try:
        evs = tr.load(str(trace_dir))
        w0, w1 = tr.window(evs)
    except RuntimeError:
        return None
    if (w1 - w0) / 1e9 != run.trace.window_s:
        return None
    return evs


def spans(evs: Sequence[tr.Ev], prefix: str = PREFIX) -> List[tr.Ev]:
    """Host spans whose name starts with ``prefix``."""
    return [e for e in evs if not tr.is_device(e) and e.name.startswith(prefix)]


def idle(evs: Sequence[tr.Ev]) -> Dict[str, List[Tuple[float, float]]]:
    """Per device plane, the intervals of the traced window in which no
    operation ran (as ``trace.summarize`` counts them)."""
    w0, w1 = tr.window(evs)
    out = {}
    for plane in tr.device_planes(evs):
        ops = [e for e in evs if e.plane == plane and e.line == tr.OPS_LINE]
        if not ops:
            ops = [e for e in evs if e.plane == plane
                   and e.line == tr.MODULES_LINE]
        busy = tr.clip(tr.merge([(e.t0, e.t1) for e in ops]), w0, w1)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        out[plane] = [(edges[i], edges[i + 1])
                      for i in range(0, len(edges), 2)
                      if edges[i + 1] > edges[i]]
    return out


def overlap(a: Sequence[Sequence[float]], b: Sequence[Sequence[float]]
            ) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_program(evs: Sequence[tr.Ev]) -> Optional[float]:
    """Share of the traced window in which the device ran nothing while the
    host was inside a ``serve.*`` span (mean over device planes); None
    where the trace holds no such span."""
    sp = spans(evs)
    if not sp:
        return None
    w0, w1 = tr.window(evs)
    inside = tr.merge([(s.t0, s.t1) for s in sp])
    gaps = idle(evs)
    return sum(overlap(g, inside) for g in gaps.values()) / len(gaps) / (
        w1 - w0)


def within(evs: Sequence[tr.Ev], name: str) -> List[tr.Ev]:
    """The host spans ``name`` wholly inside the traced window."""
    w0, w1 = tr.window(evs)
    return [s for s in spans(evs, name) if s.name == name
            and w0 <= s.t0 and s.t1 <= w1]


def labelled_gaps(evs: Sequence[tr.Ev], top: int = 10
                  ) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps (seconds), each named by the innermost
    host span, the benchmark's (``cb.*``) or the program's (``serve.*``),
    around its middle."""
    around = [e for e in spans(evs, "cb.") if e.name != tr.SLICE_SPAN] + \
        spans(evs)
    gaps = [g for gs in idle(evs).values() for g in gs]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [(tr.label(g, around), (g[1] - g[0]) / 1e9) for g in gaps]
