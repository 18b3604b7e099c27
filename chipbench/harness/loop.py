"""Drive one replica of the served path on the wall clock.

The program's ``SchedulerCore`` keeps a virtual clock that moves only by the
host-timed engine calls, so a latency read from it leaves out slot
insertion, token readbacks and all scheduling.  Here the core runs in its
incremental mode (``begin`` / ``offer`` / ``drain_until``), one scheduling
event per ``drain_until`` call, and every time is read from the host's
clock:

* a request is offered when it is due, stamped with the core's clock at that
  moment, so the core admits it at its next event;
* each engine call is stamped where it is made, through :class:`Tap`;
* a request's first token is stamped when the host has it, which is when the
  next engine call starts; every later token when its event ends.

Latencies are wall time from when a request was due.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

import jax

from harness.traffic import Job


@dataclasses.dataclass
class Call:
    kind: str                    # "prefill" | "decode"
    t0: float                    # call made (window seconds)
    t1: float                    # call returned (dispatched)
    rows: int                    # prefill: prompt tokens; decode: slots
    lengths: tuple = ()          # decode: live slots' positions before


@dataclasses.dataclass
class Stamp:
    rid: int
    due: float
    prompt_len: int
    max_new: int
    prefill_start: Optional[float] = None
    tokens: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


class Tap:
    """Stamps the engine calls the policy makes; a pure observer."""

    def __init__(self, engine, policy, clock: Callable[[], float]):
        self.engine, self.policy, self.clock = engine, policy, clock
        self.calls: List[Call] = []
        self._prefill = engine.prefill_one
        self._decode = engine.decode_batch
        engine.prefill_one = self.prefill_one
        engine.decode_batch = self.decode_batch

    def detach(self) -> None:
        del self.engine.prefill_one
        del self.engine.decode_batch

    def prefill_one(self, tokens):
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("cb.prefill"):
            out = self._prefill(tokens)
        self.calls.append(Call("prefill", t0, self.clock(), tokens.shape[1]))
        return out

    def decode_batch(self, cache, tokens):
        p = self.policy
        lengths = tuple(len(r.prompt) + p.slot_emitted[s] - 1
                        for s, r in enumerate(p.slot_req) if r is not None)
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("cb.decode"):
            out = self._decode(cache, tokens)
        self.calls.append(Call("decode", t0, self.clock(), len(p.slot_req),
                               lengths))
        return out


@contextlib.contextmanager
def count_compiles():
    """Backend compilations inside the block."""
    seen = []

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


class Driver:
    """One replica's ``SchedulerCore`` + policy + engine, on the wall clock."""

    def __init__(self, core, request_cls):
        self.core = core
        self.policy = core.policy
        self.Request = request_cls
        self.t_open = time.perf_counter()
        self.tap = Tap(core.engine, self.policy, self.now)
        self.stamps: Dict[int, Stamp] = {}
        self.prompts: Dict[int, object] = {}
        self.events: List[tuple] = []           # (t_start, t_end)
        self.on_event: Optional[Callable[[float], None]] = None

    def now(self) -> float:
        return time.perf_counter() - self.t_open

    def restart(self) -> None:
        """A fresh run on the same core: empty slots, queue and records."""
        self.core.begin()
        self.tap.calls.clear()
        self.stamps.clear()
        self.prompts.clear()
        self.events.clear()
        self.t_open = time.perf_counter()

    # -- the core's incremental mode -----------------------------------------
    def offer(self, job: Job, due: Optional[float] = None) -> None:
        due = self.now() if due is None else due
        self.stamps[job.rid] = Stamp(job.rid, due, len(job.prompt),
                                     job.max_new)
        self.prompts[job.rid] = job.prompt
        self.core.offer(self.Request(rid=job.rid, prompt=job.prompt,
                                     max_new_tokens=job.max_new,
                                     arrival_s=self.core.clock))

    def busy(self) -> bool:
        return self.core.has_pending() or self.policy.active(self.core)

    def event(self) -> None:
        """One scheduling event: admissions, then one decode step."""
        p, core = self.policy, self.core
        before = {r.rid: p.slot_emitted[s]
                  for s, r in enumerate(p.slot_req) if r is not None}
        n_calls, n_resp = len(self.tap.calls), len(core.responses)
        t0 = self.now()
        with jax.profiler.TraceAnnotation("cb.event"):
            core.drain_until(math.nextafter(core.clock, math.inf))
        t1 = self.now()
        self.events.append((t0, t1))
        after = {r.rid: p.slot_emitted[s]
                 for s, r in enumerate(p.slot_req) if r is not None}
        for resp in core.responses[n_resp:]:
            after[resp.rid] = len(resp.tokens)
        calls = self.tap.calls[n_calls:]
        prefills = [i for i, c in enumerate(calls) if c.kind == "prefill"]
        admitted = sorted(rid for rid in after if rid not in before)
        if len(admitted) != len(prefills):
            raise RuntimeError(f"{len(admitted)} admissions but "
                               f"{len(prefills)} prefill calls in one event")
        for rid, i in zip(admitted, prefills):
            st = self.stamps[rid]
            st.prefill_start = calls[i].t0
            # the host holds the first token once it moves on
            nxt = calls[i + 1].t0 if i + 1 < len(calls) else t1
            st.tokens.append(nxt)
        for rid, n in after.items():
            st = self.stamps[rid]
            st.tokens.extend([t1] * (n - len(st.tokens)))
        if self.on_event is not None:
            self.on_event(t1)

    def run(self, jobs: List[Job], seconds: float, drain_cap_s: float,
                 at_boundary: Callable[[float], None] = lambda t: None
                 ) -> float:
        """Offer ``jobs`` when due; after ``seconds`` serve on until every
        offered request is done, for at most ``drain_cap_s`` more.  Returns
        the time the run stopped."""
        i = 0
        while True:
            t = self.now()
            while i < len(jobs) and jobs[i].due_s <= t:
                self.offer(jobs[i], due=jobs[i].due_s)
                i += 1
            at_boundary(t)
            if self.busy():
                self.event()
            elif i < len(jobs):
                time.sleep(max(0.0, min(jobs[i].due_s - t, 0.05)))
            else:
                return t
            if t > seconds + drain_cap_s:
                return t
