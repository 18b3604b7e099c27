"""A run's record, read by the metric readers (``chipbench/metrics/``).

Every latency is wall time from when the request was due.  A tail is taken
over every sample in the run (all requests, all tokens), never over chunks.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from harness.loop import Call, Stamp
from harness.trace import Summary


@dataclasses.dataclass
class Run:
    cell: str
    cfg: dict
    traffic: dict
    peaks: dict
    window: Tuple[float, float]            # window seconds (driver clock)
    end: float                             # when serving stopped
    stamps: Dict[int, Stamp]
    calls: List[Call]
    setup_s: float
    deploy_s: float
    trace: Optional[Summary] = None
    trace_window: Optional[Tuple[float, float]] = None   # driver clock

    def due_in_window(self) -> List[Stamp]:
        """The requests the run is judged on: those due in the window."""
        w0, w1 = self.window
        return [s for s in self.stamps.values() if w0 <= s.due < w1]

    def failed(self) -> List[Stamp]:
        """Requests due in the window and not done when serving stopped."""
        return [s for s in self.due_in_window() if not s.done]

    def ttfts(self) -> List[float]:
        """Due to first token; a request that never got one counts with the
        time until serving stopped."""
        return [(s.tokens[0] if s.tokens else self.end) - s.due
                for s in self.due_in_window()]

    def gaps(self) -> List[float]:
        """Every output token after a request's first: time since that
        request's previous token."""
        return [b - a for s in self.due_in_window()
                for a, b in zip(s.tokens, s.tokens[1:])]

    def queue_waits(self) -> List[float]:
        """Due to the start of the prefill, for the requests due before a
        traced slice (starting the profiler stalls the host)."""
        end = self.trace_window[0] if self.trace_window else float("inf")
        return [s.prefill_start - s.due for s in self.due_in_window()
                if s.prefill_start is not None and s.due < end]

    # -- the traced slice ------------------------------------------------
    def traced_calls(self, kind: str) -> List[Call]:
        if self.trace_window is None:
            return []
        a, b = self.trace_window
        return [c for c in self.calls
                if c.kind == kind and a <= c.t0 and c.t1 <= b]

    def traced_device_times(self, kind: str, part: str
                            ) -> Optional[Tuple[List[Call], List[float]]]:
        """The traced engine calls of ``kind`` and the device time of each
        run of the program named ``part``, matched in order; None where the
        trace holds none or the counts disagree."""
        if self.trace is None:
            return None
        calls = self.traced_calls(kind)
        times = self.trace.module_times(part)
        if not calls or len(calls) != len(times):
            return None
        return calls, times


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile over every value given."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
