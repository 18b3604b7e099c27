"""Reduce a profiler trace to device busy time, per-program time and gaps.

The trace is read with ``jax.profiler.ProfileData`` into plain events
``(plane, line, name, start_ns, dur_ns)``, so the reduction below is plain
Python and is tested on a small recorded trace.  Device planes are named
``/device:...``; on them the ``XLA Ops`` line holds one event per operation
and the ``XLA Modules`` line one per run of a compiled program.  Host spans
that the benchmark writes (``cb.*``) sit on the host plane, on the same
clock, and name what the host was doing in each idle gap.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SLICE_SPAN = "cb.slice"


@dataclasses.dataclass
class Ev:
    plane: str
    line: str
    name: str
    t0: float                    # ns
    dur: float                   # ns

    @property
    def t1(self) -> float:
        return self.t0 + self.dur


def load(trace_dir: str) -> List[Ev]:
    """Every event of the one ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns)))
    return out


def to_rows(evs: Sequence[Ev]) -> list:
    return [[e.plane, e.line, e.name, e.t0, e.dur] for e in evs]


def from_rows(rows) -> List[Ev]:
    return [Ev(*r) for r in rows]


def is_device(e: Ev) -> bool:
    return e.plane.startswith("/device:") and "CPU" not in e.plane


def device_planes(evs: Sequence[Ev]) -> List[str]:
    return sorted({e.plane for e in evs if is_device(e)})


def merge(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, w0: float, w1: float) -> List[List[float]]:
    return [[max(a, w0), min(b, w1)] for a, b in intervals
            if b > w0 and a < w1]


def window(evs: Sequence[Ev]) -> Tuple[float, float]:
    """The traced window: the benchmark's ``cb.slice`` host span."""
    spans = [e for e in evs if e.name == SLICE_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {SLICE_SPAN} span, "
                           f"found {len(spans)}")
    return spans[0].t0, spans[0].t1


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over device planes
    modules: Dict[str, List[float]]        # program name -> durations (s)
    ops: Dict[str, float]                  # op name -> seconds, all planes
    gaps: List[Tuple[str, float]]          # (host span, seconds), longest first
    # (program name, op name) -> (runs, seconds) of the ops inside each run
    # of a program
    nested: Dict[Tuple[str, str], Tuple[int, float]] = dataclasses.field(
        default_factory=dict)
    def module_times(self, part: str) -> List[float]:
        """Durations of every run of the programs whose name holds
        ``part`` (``prefill_fn``, ``decode_fn``)."""
        out: List[float] = []
        for name, d in sorted(self.modules.items()):
            if part in name:
                out += d
        return out

    def op_time(self, prefix: str) -> Optional[float]:
        """Device seconds of the ops whose name starts with ``prefix``."""
        hits = [s for n, s in self.ops.items() if n.startswith(prefix)]
        return sum(hits) if hits else None

    def op_in(self, part: str, prefix: str) -> Tuple[int, float]:
        """Runs and device seconds of the ops whose name starts with
        ``prefix``, inside runs of the programs whose name holds ``part``."""
        hits = [v for (m, o), v in self.nested.items()
                if part in m and o.startswith(prefix)]
        return sum(n for n, _ in hits), sum(t for _, t in hits)


def summarize(evs: Sequence[Ev], top: int = 10) -> Summary:
    """Busy union, per-program and per-op time, and labelled idle gaps,
    inside the traced window."""
    w0, w1 = window(evs)
    planes = device_planes(evs)
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    busy_total = 0.0
    all_gaps: List[Tuple[float, float]] = []
    for plane in planes:
        ops = [e for e in evs if e.plane == plane and e.line == OPS_LINE]
        if not ops:
            ops = [e for e in evs if e.plane == plane
                   and e.line == MODULES_LINE]
        busy = clip(merge([(e.t0, e.t1) for e in ops]), w0, w1)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        all_gaps += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    modules: Dict[str, List[float]] = {}
    ops_t: Dict[str, float] = {}
    nested: Dict[Tuple[str, str], Tuple[int, float]] = {}
    runs = {p: sorted((e.t0, e.t1, e.name) for e in evs if e.plane == p
                      and e.line == MODULES_LINE) for p in planes}
    for e in evs:
        if not is_device(e) or e.t1 <= w0 or e.t0 >= w1:
            continue
        if e.line == MODULES_LINE:
            modules.setdefault(e.name, []).append(e.dur / 1e9)
        elif e.line == OPS_LINE:
            name = op_name(e.name)
            ops_t[name] = ops_t.get(name, 0.0) + e.dur / 1e9
            rs = runs.get(e.plane, [])
            i = bisect.bisect_right(rs, (e.t0, float("inf"), "")) - 1
            if i >= 0 and rs[i][0] <= e.t0 < rs[i][1]:
                n, t = nested.get((rs[i][2], name), (0, 0.0))
                nested[(rs[i][2], name)] = (n + 1, t + e.dur / 1e9)
    spans = [e for e in evs if not is_device(e) and e.name.startswith("cb.")
             and e.name != SLICE_SPAN]
    gaps = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    labelled = [(label(g, spans), (g[1] - g[0]) / 1e9) for g in gaps]
    return Summary((w1 - w0) / 1e9, busy_total / len(planes) / 1e9,
                   modules, ops_t, labelled, nested)


def op_name(text: str) -> str:
    """The HLO instruction's name (``%int8_matmul.43``) from the op's text
    (``%int8_matmul.43 = bf16[8,1024]... custom-call(...)``)."""
    return text.split(" = ", 1)[0]


def label(gap: Tuple[float, float], spans: Sequence[Ev]) -> str:
    """The innermost benchmark host span around the gap's middle."""
    mid = (gap[0] + gap[1]) / 2
    around = [s for s in spans if s.t0 <= mid <= s.t1]
    if not around:
        return "between events"
    return min(around, key=lambda s: s.dur).name


def breakdown(s: Summary, top: int = 10) -> dict:
    ops = sorted(s.ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in s.gaps[:top]]}
