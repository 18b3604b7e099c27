"""Host spans (``serve.*``) and counters on the served path: what a profiler
trace of one continuous-batching run holds, the counts the core keeps, the
compile record, and that spans change nothing the run computes."""

import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import engines
from repro.core.engines import CompiledEngine
from repro.models import init_params
from repro.serving import api, scheduler
from repro.serving.core import SchedulerCore
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchPolicy
from repro.serving.stepcache import StepTimeCache, shape_bucket

ARCH = "minitron-4b-smoke"
SLOTS = 3
LENS = (5, 9, 16, 12, 7)
NEWS = (3, 5, 2, 4, 6)
# by hand, 3 slots admitted FIFO at t=0: steps 1-4 run 3 live slots (r2
# retires after step 1, r0 after 2, r1 and r3 after 4; r3 and r4 take the
# freed slots), then r4 alone for steps 5-7
HAND = {"decode_steps": 7, "live_slot_steps": 15, "slot_steps": 21,
        "admissions": 5, "d2h": 5 + 15, "state_bytes_inserted": 0}


@pytest.fixture(scope="module")
def model():
    cfg = get_arch(ARCH)
    assert cfg.num_layers == 2
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _workload(cfg):
    rs = np.random.RandomState(0)
    return [Request(rid=i, prompt=rs.randint(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=m, arrival_s=0.0)
            for i, (n, m) in enumerate(zip(LENS, NEWS))]


def _core(engine, step_cache=None):
    return SchedulerCore(engine, ContinuousBatchPolicy(SLOTS, 64),
                         step_cache=step_cache)


def _host_spans(trace_dir):
    """(name, t0, t1, stats) of every ``serve.*`` event on a host plane."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One run under the profiler, then a second on the same engine."""
    cfg, params = model
    engine = CompiledEngine(cfg, params, max_seq=64)
    d1, d2 = (str(tmp_path_factory.mktemp(n)) for n in ("first", "again"))
    core = _core(engine)
    with jax.profiler.trace(d1):
        core.run(_workload(cfg))
    counters = dict(core.counters)
    compiled = dict(engine._compiled)
    with jax.profiler.trace(d2):
        core.run(_workload(cfg))
    return {"spans": _host_spans(d1), "again": _host_spans(d2),
            "counters": counters, "compiled": compiled,
            "compiled_after": dict(engine._compiled)}


def test_one_decode_readback_and_step_span_per_decode_step(traced):
    spans = traced["spans"]
    steps = HAND["decode_steps"]
    for name in ("serve.step", "serve.admit", "serve.decode",
                 "serve.decode.wait", "serve.readback"):
        assert len(_named(spans, name)) == steps, name
    # one bill per decode step and one per prefill
    assert len(_named(spans, "serve.bill")) == steps + len(LENS)
    assert [s[3]["live"] for s in _named(spans, "serve.readback")] == \
        [3, 3, 3, 3, 1, 1, 1]


def test_one_d2h_per_live_slot_per_step_inside_its_readback(traced):
    spans = traced["spans"]
    readbacks = _named(spans, "serve.readback")
    d2h = _named(spans, "serve.d2h")
    assert len(d2h) == HAND["live_slot_steps"]
    for rb in readbacks:
        inside = [d for d in d2h if rb[1] <= d[1] and d[2] <= rb[2]]
        assert len(inside) == rb[3]["live"]
    assert all(any(rb[1] <= d[1] and d[2] <= rb[2] for rb in readbacks)
               for d in d2h)
    # every retirement sits in a readback, and carries its request
    retire = _named(spans, "serve.retire")
    assert sorted(s[3]["rid"] for s in retire) == list(range(len(LENS)))
    assert all(any(rb[1] <= r[1] and r[2] <= rb[2] for rb in readbacks)
               for r in retire)


def test_a_prefill_span_per_admission_with_its_rid_and_bucket(traced):
    spans = traced["spans"]
    got = {s[3]["rid"]: (s[3]["bucket"], s[3]["tokens"])
           for s in _named(spans, "serve.prefill")}
    assert got == {i: (shape_bucket(n), n) for i, n in enumerate(LENS)}
    for name in ("serve.prefill.wait", "serve.insert", "serve.first_token"):
        assert sorted(s[3]["rid"] for s in _named(spans, name)) == \
            list(range(len(LENS))), name
    slots = {s[3]["rid"]: s[3]["slot"] for s in _named(spans, "serve.insert")}
    assert slots == {0: 0, 1: 1, 2: 2, 3: 2, 4: 0}


def test_counters_match_a_hand_count(traced):
    assert traced["counters"] == HAND
    occupancy = HAND["live_slot_steps"] / HAND["slot_steps"]
    assert occupancy == pytest.approx(15 / 21)


def test_compile_span_fires_once_per_new_shape_then_never(traced):
    compiles = [(s[3]["entry"], s[3]["shape"])
                for s in _named(traced["spans"], "serve.compile")]
    buckets = sorted({shape_bucket(n) for n in LENS})
    want = [("prefill", str((1, b))) for b in buckets] + \
        [("decode", str((SLOTS,)))]
    assert sorted(compiles) == sorted(want)
    assert _named(traced["again"], "serve.compile") == []
    compiled = traced["compiled"]
    assert sorted((e, str(s)) for e, s in compiled) == sorted(want)
    assert compiled[("decode", (SLOTS,))] == HAND["decode_steps"]
    assert sum(n for (e, _), n in compiled.items() if e == "prefill") == \
        len(LENS)
    # the second run adds calls, no shape
    assert set(traced["compiled_after"]) == set(compiled)
    assert traced["compiled_after"][("decode", (SLOTS,))] == \
        2 * HAND["decode_steps"]


def test_counters_reset_with_each_run(model):
    cfg, params = model
    engine = CompiledEngine(cfg, params, max_seq=64)
    c = _core(engine)
    c.run(_workload(cfg))
    c.run(_workload(cfg))
    assert c.counters == HAND
    c.begin()
    assert set(c.counters.values()) == {0}


def _null_span(*_a, **_k):
    return contextlib.nullcontext()


def _timeline(metrics):
    return [(r.rid, r.start_s, r.first_token_s, r.done_s,
             tuple(np.asarray(r.tokens).tolist())) for r in metrics.responses]


@pytest.mark.parametrize("profiler", [False, True])
def test_spans_change_no_token_and_no_virtual_time(model, monkeypatch,
                                                   tmp_path, profiler):
    """Against a run whose spans are no-ops: the same tokens when the
    engine runs, and the same virtual timeline and joules on a replayed
    step cache (measured durations differ from run to run)."""
    cfg, params = model
    engine = CompiledEngine(cfg, params, max_seq=64)

    def replay_cache():
        cache = StepTimeCache()
        for n in LENS:
            cache.put(("prefill1", shape_bucket(n)), (0.01,))
        cache.put(("decode", SLOTS), (0.02,))
        return cache

    def runs():
        real = _core(engine).run(_workload(cfg))
        core = _core(engine, replay_cache())
        replayed = core.run(_workload(cfg))
        return real, replayed, dict(core.counters)

    with monkeypatch.context() as m:
        for mod in (scheduler, engines):
            m.setattr(mod, "span", _null_span)
        base_real, base_replay, _ = runs()
    ctx = jax.profiler.trace(str(tmp_path)) if profiler \
        else contextlib.nullcontext()
    with ctx:
        real, replayed, counters = runs()
    assert [t[-1] for t in _timeline(real)] == \
        [t[-1] for t in _timeline(base_real)]
    assert _timeline(replayed) == _timeline(base_replay)
    assert replayed.energy_j == base_replay.energy_j
    assert replayed.meter.per_request_j == base_replay.meter.per_request_j
    # replayed steps count steps and occupancy, and read nothing back
    assert counters == dict(HAND, admissions=5, d2h=0)


def test_deploy_times_its_registry_round_trip(model, tmp_path):
    cfg, params = model
    ep = api.EndpointSpec(name="m", arch=ARCH, format="rsm_int8",
                          policy="continuous_batch", max_batch=2,
                          max_seq=64, step_cache=False)
    session = api.ServingSession(registry_root=str(tmp_path))
    assert session.deploy_phases_s == {"save": 0.0, "load": 0.0}
    session.deploy(api.ServingSpec(endpoints=(ep,)), params={"m": params})
    phases = session.deploy_phases_s
    assert set(phases) == {"save", "load"}
    assert phases["save"] > 0 and phases["load"] > 0
    # a re-deploy that hits the engine memo writes and reads nothing
    session.deploy(api.ServingSpec(endpoints=(ep,)), params={"m": params})
    assert session.deploy_phases_s == {"save": 0.0, "load": 0.0}

