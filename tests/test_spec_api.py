"""Contract tests for the declarative ServingSpec / ServingSession API.

Covers the redesign's load-bearing guarantees:
  * spec serialization — ``from_json(to_json(spec)) == spec``;
  * eager validation — unknown policy/router, duplicate endpoint names,
    negative budgets, and SLO budgets tighter than the measured floor all
    raise ``SpecError`` naming the offending field path;
  * sweep expansion — ``{path: [values]}`` grids expand to validated
    variants and reject unknown paths/endpoints;
  * adapter equivalence — ``CloudService.predict`` (now a shim) produces
    the same joules and the same retirement timeline as driving the
    session directly;
  * heterogeneous fleets — ``EndpointSpec.format`` really selects the
    replica weights (int8 bulk + fp32 quality behind one router) with
    per-replica meter provenance;
  * TD1 billing — the container choice bills its energy overhead and
    cold start into the report instead of being a doc-only artifact.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.add import (
    Deployment,
    ModelFormat,
    RequestProcessing,
    ServingInfrastructure,
)
from repro.core.engines import GenerationResult
from repro.serving.api import (
    AutoscaleSpec,
    EndpointSpec,
    ServingSession,
    ServingSpec,
    SLOClass,
    SpecError,
    endpoint_from_deployment,
    sweep,
    with_override,
)
from repro.serving.cloud import CloudService
from repro.serving.request import synth_workload
from repro.serving.stepcache import StepTimeCache, shape_bucket

ARCH = "minitron-4b-smoke"


class FakeEngine:
    """Deterministic timings, no model — session mechanics only."""

    cfg = None

    def __init__(self, prefill_s=0.01, step_s=0.005):
        self.prefill_s = prefill_s
        self.step_s = step_s

    def generate(self, tokens, max_new):
        B = tokens.shape[0]
        return GenerationResult(
            tokens=np.ones((B, max_new), np.int32),
            prefill_s=self.prefill_s,
            decode_s=self.step_s * (max_new - 1),
            n_steps=max_new,
        )


def base_spec(**kw) -> ServingSpec:
    eps = kw.pop("endpoints", None) or (
        EndpointSpec(name="chat", arch=ARCH, max_batch=8,
                     slo_classes={"interactive": SLOClass(slo_ms=100.0),
                                  "batch": SLOClass(slo_ms=None)}),
        EndpointSpec(name="bulk", arch=ARCH, policy="adaptive_batch"),
    )
    return ServingSpec(endpoints=eps, **kw)


# -- serialization -------------------------------------------------------------


def test_spec_json_roundtrip():
    spec = base_spec(router="greenest", ttft_budget_s=0.2,
                     active_power_w=90.0, idle_power_w=12.0)
    spec = with_override(spec, "endpoints.bulk.format", "rsm_int8")
    spec = with_override(spec, "endpoints.chat.autoscale.max_replicas", 6)
    back = ServingSpec.from_json(spec.to_json())
    assert back == spec
    assert back.endpoint("bulk").format == "rsm_int8"
    assert back.endpoint("chat").autoscale.max_replicas == 6
    assert back.endpoint("chat").slo_classes["interactive"].slo_ms == 100.0
    # endpoints survive as a tuple (list-built specs are coerced)
    assert isinstance(back.endpoints, tuple)
    assert ServingSpec.from_json(back.to_json()) == back


def test_from_dict_unknown_field_names_path():
    """A typo in hand-edited spec JSON raises SpecError with the path, not
    a bare TypeError from __init__."""
    doc = base_spec().to_dict()
    doc["endpoints"][0]["polcy"] = "dynamic_batch"
    with pytest.raises(SpecError, match=r"endpoints\[chat\].polcy"):
        ServingSpec.from_dict(doc)
    with pytest.raises(SpecError, match="spec.rooter"):
        ServingSpec.from_dict({"endpoints": [], "rooter": "greenest"})
    with pytest.raises(SpecError, match=r"autoscale.widnow_s"):
        ServingSpec.from_dict({"endpoints": [
            {"name": "m", "arch": ARCH, "autoscale": {"widnow_s": 1.0}}]})


def test_spec_list_endpoints_coerced():
    ep = EndpointSpec(name="m", arch=ARCH)
    assert ServingSpec(endpoints=[ep]) == ServingSpec(endpoints=(ep,))


# -- validation ----------------------------------------------------------------


@pytest.mark.parametrize("mutate,field", [
    (lambda s: dataclasses.replace(s, router="zigzag"), "router"),
    (lambda s: dataclasses.replace(s, ttft_budget_s=-1.0), "ttft_budget_s"),
    (lambda s: with_override(s, "endpoints.chat.policy", "mystery"),
     "endpoints[chat].policy"),
    (lambda s: with_override(s, "endpoints.chat.format", "onnx"),
     "endpoints[chat].format"),
    (lambda s: with_override(s, "endpoints.chat.ttft_slo_ms", -5.0),
     "endpoints[chat].ttft_slo_ms"),
    (lambda s: with_override(s, "endpoints.bulk.autoscale",
                             AutoscaleSpec(min_replicas=3, max_replicas=1)),
     "endpoints[bulk].autoscale.min_replicas"),
    (lambda s: with_override(s, "endpoints.bulk.autoscale",
                             AutoscaleSpec(window_s=-0.5)),
     "endpoints[bulk].autoscale.window_s"),
    (lambda s: with_override(s, "endpoints.chat.slo_classes",
                             {"rt": SLOClass(slo_ms=-10.0)}),
     "endpoints[chat].slo_classes[rt].slo_ms"),
])
def test_validation_names_offending_field(mutate, field):
    with pytest.raises(SpecError) as e:
        mutate(base_spec()).validate()
    assert field in str(e.value)
    assert e.value.field == field


def test_duplicate_endpoint_names_rejected():
    ep = EndpointSpec(name="chat", arch=ARCH)
    with pytest.raises(SpecError, match=r"endpoints\[1\].name.*duplicate"):
        ServingSpec(endpoints=(ep, dataclasses.replace(ep))).validate()


def test_disagreeing_autoscale_windows_rejected():
    spec = base_spec()
    spec = with_override(spec, "endpoints.bulk.autoscale",
                         AutoscaleSpec(window_s=2.0))
    with pytest.raises(SpecError, match="window_s"):
        spec.validate()


def test_slo_tighter_than_measured_floor():
    """A calibrated floor above the class budget must fail with the class's
    field path before any request is simulated."""
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="chat", arch=ARCH, ttft_slo_ms=5000.0,
                     slo_classes={"rt": SLOClass(slo_ms=10.0)}),))
    session = ServingSession()
    session.deploy(spec, engines={"chat": FakeEngine()})
    cache = StepTimeCache()
    cache.put(("generate", 1, shape_bucket(8), 4), (0.05, 0.015))  # 50ms floor
    session.warm("chat", cache)
    session.submit("chat", synth_workload(5, 8, 4, 100, rate_per_s=50, seed=0))
    with pytest.raises(SpecError) as e:
        session.run()
    assert e.value.field == "endpoints[chat].slo_classes[rt].slo_ms"
    # the opt-in spec-global budget is floor-checked too
    g = ServingSession()
    g.deploy(dataclasses.replace(
        spec, ttft_budget_s=0.01,
        endpoints=(dataclasses.replace(spec.endpoints[0], slo_classes={}),)),
        engines={"chat": FakeEngine()})
    g.warm("chat", cache)
    g.submit("chat", synth_workload(5, 8, 4, 100, rate_per_s=50, seed=0))
    with pytest.raises(SpecError) as e2:
        g.run()
    assert e2.value.field == "ttft_budget_s"
    # loosening the class budget makes the same session runnable
    session.deploy(with_override(spec, "endpoints.chat.slo_classes",
                                 {"rt": SLOClass(slo_ms=500.0)}),
                   engines={"chat": FakeEngine()})
    session.warm("chat", cache)
    session.submit("chat", synth_workload(5, 8, 4, 100, rate_per_s=50, seed=0))
    assert len(session.run().endpoints["chat"].metrics.responses) == 5


def test_autoscale_spec_folds_mmc_sizing():
    """AutoscaleSpec.initial_pool is the old AutoscalePolicy.replicas_for:
    M/M/c sizing unless a hint pins the pool."""
    a = AutoscaleSpec(min_replicas=1, max_replicas=4, target_utilization=0.7)
    assert a.initial_pool(rate_per_s=100.0, service_time_s=0.01) == 2
    assert a.initial_pool(rate_per_s=1000.0, service_time_s=0.01) == 4  # clamp
    assert a.initial_pool(rate_per_s=0.1, service_time_s=0.01) == 1    # floor
    pinned = dataclasses.replace(a, replicas_hint=3)
    assert pinned.initial_pool(1000.0, 0.01) == 3


# -- sweeps --------------------------------------------------------------------


def test_sweep_expands_validated_grid():
    grid = sweep(base_spec(), {
        "router": ["round_robin", "greenest"],
        "endpoints.bulk.format": ["rsm", "rsm_int8"],
    })
    assert len(grid) == 4
    combos = {(a["router"], a["endpoints.bulk.format"]) for a, _ in grid}
    assert len(combos) == 4
    for assignment, variant in grid:
        assert variant.router == assignment["router"]
        assert variant.endpoint("bulk").format == \
            assignment["endpoints.bulk.format"]
        # untouched endpoints keep their fields
        assert variant.endpoint("chat").format == "rsm"


def test_sweep_rejects_unknown_paths():
    with pytest.raises(SpecError, match="no field"):
        sweep(base_spec(), {"endpoints.chat.exotic_knob": [1]})
    with pytest.raises(SpecError, match="no endpoint named"):
        with_override(base_spec(), "endpoints.ghost.format", "rsm")
    # infeasible cells fail at grid construction, naming the field
    with pytest.raises(SpecError, match=r"endpoints\[chat\].policy"):
        sweep(base_spec(), {"endpoints.chat.policy": ["warp_drive"]})


def test_star_override_hits_every_endpoint():
    spec = with_override(base_spec(), "endpoints.*.max_seq", 64)
    assert all(ep.max_seq == 64 for ep in spec.endpoints)


# -- mapping-path overrides (the rate x SLO sweep axes) ------------------------


def test_mapping_override_star_hits_every_slo_class():
    base = base_spec()
    spec = with_override(base, "endpoints.chat.slo_classes.*.slo_ms", 80.0)
    assert all(c.slo_ms == 80.0
               for c in spec.endpoint("chat").slo_classes.values())
    # copy-on-write: the original spec's classes are untouched
    assert base.endpoint("chat").slo_classes["interactive"].slo_ms == 100.0


def test_mapping_override_named_key_leaves_siblings():
    spec = with_override(base_spec(),
                         "endpoints.chat.slo_classes.interactive.slo_ms",
                         55.0)
    classes = spec.endpoint("chat").slo_classes
    assert classes["interactive"].slo_ms == 55.0
    assert classes["batch"].slo_ms is None


def test_mapping_override_unknown_key_rejected():
    with pytest.raises(SpecError, match="no key 'premium'"):
        with_override(base_spec(),
                      "endpoints.chat.slo_classes.premium.slo_ms", 10.0)


def test_mapping_override_needs_trailing_field():
    with pytest.raises(SpecError, match="field after the key"):
        with_override(base_spec(),
                      "endpoints.chat.slo_classes.interactive", 10.0)


def test_override_cannot_descend_into_unset_field():
    # bulk declares no workload; the path must fail loudly, not invent one
    with pytest.raises(SpecError, match="unset"):
        with_override(base_spec(), "endpoints.bulk.workload.rate_per_s",
                      100.0)


def test_sweep_rate_x_slo_axes():
    from repro.workload.generators import WorkloadSpec

    base = base_spec(endpoints=(
        EndpointSpec(name="api", arch=ARCH, max_batch=8,
                     slo_classes={"interactive": SLOClass(slo_ms=100.0)},
                     workload=WorkloadSpec(kind="poisson", n=10,
                                           rate_per_s=50.0, seed=3)),
    ))
    grid = sweep(base, {
        "endpoints.*.workload.rate_per_s": [100.0, 200.0],
        "endpoints.*.slo_classes.*.slo_ms": [60.0, 120.0],
    })
    assert len(grid) == 4
    for assignment, variant in grid:
        ep = variant.endpoint("api")
        assert ep.workload.rate_per_s == \
            assignment["endpoints.*.workload.rate_per_s"]
        assert ep.slo_classes["interactive"].slo_ms == \
            assignment["endpoints.*.slo_classes.*.slo_ms"]


# -- adapter equivalence -------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_params():
    import jax

    from repro.configs import get_arch
    from repro.models import init_params

    cfg = get_arch(ARCH)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_cloud_predict_equals_direct_session(tmp_path, smoke_params):
    """The CloudService shim and a hand-built session must produce the same
    joules and the same retirement timeline on an identical workload."""
    cfg, params = smoke_params
    cloud = CloudService(str(tmp_path / "registry"))
    cloud.upload_model("m", 1, params, ModelFormat.RSM)
    dep = Deployment(arch=ARCH, si=ServingInfrastructure.SI4_CLOUD_SERVICE,
                     request_processing=RequestProcessing.DYNAMIC_BATCH,
                     max_batch=4, max_seq=64, min_replicas=1, max_replicas=3,
                     autoscale_window_s=0.5, cold_start_s=0.1)
    cloud.deploy("m", 1, dep, template_params=params)
    cloud.calibrate_endpoint("m", batch_sizes=[1, 2, 3, 4], prompt_len=8,
                             max_new=3)
    wl = lambda: synth_workload(60, 8, 3, cfg.vocab_size,  # noqa: E731
                                rate_per_s=200, seed=7)
    old = cloud.predict("m", wl())

    spec = ServingSpec(endpoints=(endpoint_from_deployment("m", dep),),
                       router=dep.router)
    session = ServingSession()
    session.deploy(spec, engines={"m": cloud.endpoints["m"]["engine"]})
    session.warm("m", cloud.endpoints["m"]["warm_cache"])
    session.submit("m", wl())
    new = session.run().endpoints["m"].metrics

    assert len(old.responses) == len(new.responses) == 60
    assert old.meter.total_j == pytest.approx(new.meter.total_j, rel=1e-9)
    assert old.meter.active_j == pytest.approx(new.meter.active_j, rel=1e-9)
    old_done = sorted((r.rid, round(r.done_s, 9)) for r in old.responses)
    new_done = sorted((r.rid, round(r.done_s, 9)) for r in new.responses)
    assert old_done == new_done


def test_server_handle_fixed_single_replica(smoke_params):
    """The SI3 server adapter serves through the session on exactly one
    replica — no autoscaling, all requests answered."""
    from repro.serving.server import ModelPackage, ServingServer

    cfg, params = smoke_params
    warm = StepTimeCache()
    for b in (1, 2, 3, 4):
        warm.put(("generate", b, shape_bucket(8), 3), (0.01 * b, 0.01))
    dep = Deployment(arch=ARCH, si=ServingInfrastructure.SI3_DL_SERVER,
                     request_processing=RequestProcessing.DYNAMIC_BATCH,
                     max_batch=4, max_seq=64)
    srv = ServingServer(dep)
    srv.register(ModelPackage(name="m", arch=ARCH, params=params, max_seq=64),
                 step_cache=warm)
    wl = synth_workload(30, 8, 3, cfg.vocab_size, rate_per_s=100, seed=5)
    m = srv.handle("m", wl)
    assert len(m.responses) == 30
    assert m.fleet["replicas_created"] == 1
    assert m.fleet["cold_starts"] == 0
    assert m.meter.total_j > 0


# -- heterogeneous fleets (TD2 really selects the weights) ---------------------


def test_heterogeneous_int8_fp32_fleet(tmp_path, smoke_params):
    """One router, two formats: the bulk endpoint serves QTensor (int8)
    weights, the chat endpoint full precision, and the merged meter keeps
    per-replica provenance for both."""
    import jax

    from repro.serving.formats import QTensor

    cfg, params = smoke_params
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="chat", arch=ARCH, format="rsm", model="m",
                     max_seq=64, max_batch=4,
                     autoscale=AutoscaleSpec(max_replicas=2)),
        EndpointSpec(name="bulk", arch=ARCH, format="rsm_int8", model="m",
                     max_seq=64, max_batch=4,
                     autoscale=AutoscaleSpec(max_replicas=2)),
    ), router="least_loaded")
    session = ServingSession(registry_root=str(tmp_path / "reg"))
    session.deploy(spec, params={"m": params})

    def has_qtensor(tree):
        return any(isinstance(l, QTensor) for l in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, QTensor)))

    assert has_qtensor(session.engine("bulk").params)
    assert not has_qtensor(session.engine("chat").params)
    assert session.engine("bulk") is not session.engine("chat")

    for name in ("chat", "bulk"):
        session.calibrate(name, batch_sizes=[1, 2, 4], prompt_len=8,
                          max_new=3)
    report = session.serve({
        "chat": synth_workload(40, 8, 3, cfg.vocab_size, rate_per_s=150,
                               seed=1),
        "bulk": synth_workload(40, 8, 3, cfg.vocab_size, rate_per_s=150,
                               seed=2, rid0=10_000),
    })
    assert report.fleet.n_requests == 80
    # per-replica meter provenance spans BOTH formats' replica pools
    sources = set(report.fleet.metrics.meter.by_source)
    assert any(s.startswith("chat/") for s in sources)
    assert any(s.startswith("bulk/") for s in sources)
    by_src = sum(d["active_j"] + d["idle_j"]
                 for d in report.fleet.metrics.meter.by_source.values())
    assert by_src == pytest.approx(report.fleet.j_measured, rel=1e-6)
    # each endpoint's report decomposes into only its own replicas
    assert set(report.endpoints["bulk"].j_by_replica) == \
        {s for s in sources if s.startswith("bulk/")}
    assert report.endpoints["bulk"].decisions["format"] == "rsm_int8"
    assert report.endpoints["chat"].decisions["format"] == "rsm"


def test_engine_memo_shared_across_deploys(tmp_path, smoke_params):
    """Sweeping a grid must not rebuild engines for repeated formats — but
    re-deploying the same model name with DIFFERENT weights must rebuild
    (the memo keys on params identity, never serving stale weights)."""
    import jax

    from repro.models import init_params

    cfg, params = smoke_params
    session = ServingSession(registry_root=str(tmp_path / "reg"))
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="m", arch=ARCH, format="rsm", max_seq=64),))
    session.deploy(spec, params={"m": params})
    e1 = session.engine("m")
    session.deploy(with_override(spec, "router", "greenest"),
                   params={"m": params})
    assert session.engine("m") is e1
    other = init_params(cfg, jax.random.PRNGKey(1))
    session.deploy(spec, params={"m": other})
    e2 = session.engine("m")
    assert e2 is not e1
    a = jax.tree.leaves(e1.params)[0]
    b = jax.tree.leaves(e2.params)[0]
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_engine_memo_does_not_pin_the_template(tmp_path):
    """The memo holds the template's leaves weakly: once the caller drops
    the template, no copy of it stays alive in the session."""
    import gc
    import weakref

    import jax

    from repro.configs import get_arch
    from repro.models import init_params

    params = init_params(get_arch(ARCH), jax.random.PRNGKey(3))
    leaf = weakref.ref(jax.tree.leaves(params)[0])
    session = ServingSession(registry_root=str(tmp_path / "reg"))
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="m", arch=ARCH, format="rsm", max_seq=64),))
    session.deploy(spec, params={"m": params})
    del params
    gc.collect()
    assert leaf() is None


def test_calibrate_skips_measured_shapes():
    """Two endpoints sharing one engine (or repeated sweep cells) pay for
    exactly one calibration — already-measured shapes are not re-run."""

    class CountingEngine(FakeEngine):
        calls = 0

        def generate(self, tokens, max_new):
            CountingEngine.calls += 1
            return super().generate(tokens, max_new)

    engine = CountingEngine()
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="a", arch=ARCH),
        EndpointSpec(name="b", arch=ARCH),
    ))
    session = ServingSession()
    session.deploy(spec, engines={"a": engine, "b": engine})
    session.calibrate("a", batch_sizes=[1, 2], prompt_len=8, max_new=4)
    after_first = CountingEngine.calls
    assert after_first > 0
    session.calibrate("b", batch_sizes=[1, 2], prompt_len=8, max_new=4)
    assert CountingEngine.calls == after_first


def test_floor_prefers_measured_batch_one():
    """The TTFT floor uses the real batch-1 prefill when measured; the
    linear scale-down of a batched prefill is only the no-b=1 fallback
    (a lower bound that never rejects a feasible budget)."""
    sb = shape_bucket(8)
    cache = StepTimeCache()
    cache.put(("generate", 8, sb, 4), (0.08, 0.02))   # sublinear: 0.08 at b=8
    assert cache.floor_ttft_s() == pytest.approx(0.01)  # fallback: 0.08/8
    cache.put(("generate", 1, sb, 4), (0.05, 0.01))   # true b=1 prefill
    assert cache.floor_ttft_s() == pytest.approx(0.05)


# -- TD1 billing ---------------------------------------------------------------


def test_container_choice_bills_energy_and_cold_start():
    wl = lambda: synth_workload(50, 8, 4, 100, rate_per_s=100,  # noqa: E731
                                seed=3)

    def run(container):
        spec = ServingSpec(endpoints=(
            EndpointSpec(name="m", arch=ARCH, container=container,
                         autoscale=AutoscaleSpec(max_replicas=2)),))
        session = ServingSession()
        session.deploy(spec, engines={"m": FakeEngine()})
        session.submit("m", wl())
        return session.run()

    bare = run("none")
    boxed = run("docker")
    assert bare.endpoints["m"].j_container_overhead == 0.0
    assert boxed.endpoints["m"].j_container_overhead > 0.0
    # docker bills the calibrated multiplier on measured joules
    assert boxed.endpoints["m"].j_billed == pytest.approx(
        boxed.endpoints["m"].j_measured * 1.05)
    assert boxed.fleet.j_billed > boxed.fleet.j_measured
    assert boxed.fleet.j_per_token > 0
    # and the fleet pays the container's startup on every scale-up
    session = ServingSession()
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="m", arch=ARCH, container="docker"),))
    session.deploy(spec, engines={"m": FakeEngine()})
    fe = session._fleet_endpoint(spec.endpoints[0], wl())
    assert fe.cold_start_s == pytest.approx(0.25 + 1.8)


def test_frozen_endpoint_keeps_pool_in_mixed_fleet():
    """autoscale.enabled=False pins that endpoint's pool even when it shares
    the timeline (and the fleet autoscaler) with a scaled endpoint."""
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="scaled", arch=ARCH,
                     autoscale=AutoscaleSpec(min_replicas=1, max_replicas=4,
                                             replicas_hint=1, window_s=0.25,
                                             cold_start_s=0.05)),
        EndpointSpec(name="frozen", arch=ARCH,
                     autoscale=AutoscaleSpec(enabled=False, replicas_hint=2,
                                             min_replicas=1, max_replicas=4,
                                             window_s=0.25,
                                             cold_start_s=0.05)),
    ), router="least_loaded")
    session = ServingSession()
    session.deploy(spec, engines={"scaled": FakeEngine(),
                                  "frozen": FakeEngine()})
    report = session.serve({
        "scaled": synth_workload(400, 8, 4, 100, rate_per_s=600, seed=6),
        "frozen": synth_workload(400, 8, 4, 100, rate_per_s=600, seed=7,
                                 rid0=10_000),
    })
    frozen = report.endpoints["frozen"].metrics.fleet
    assert frozen["replicas_created"] == 2
    assert frozen["scale_events"] == []
    # the scaled neighbour really was autoscaled on the same timeline
    assert report.endpoints["scaled"].metrics.fleet["scale_events"]


def test_global_ttft_budget_reaches_the_policy():
    """With no endpoint budget, the spec-global ttft_budget_s must steer the
    scheduling policy's batch sizing, not only the router."""
    spec = ServingSpec(
        endpoints=(EndpointSpec(name="m", arch=ARCH, policy="adaptive_batch",
                                ttft_slo_ms=None),),
        ttft_budget_s=0.05,
    ).validate()
    session = ServingSession()
    session.deploy(spec, engines={"m": FakeEngine()})
    fe = session._fleet_endpoint(spec.endpoints[0], [])
    assert fe.policy_factory().ttft_slo_s == pytest.approx(0.05)
    assert fe.ttft_slo_s == pytest.approx(0.05)


def test_submit_slo_class_does_not_mutate_caller_requests():
    spec = ServingSpec(endpoints=(
        EndpointSpec(name="m", arch=ARCH,
                     slo_classes={"rt": SLOClass(slo_ms=25.0)}),))
    session = ServingSession()
    session.deploy(spec, engines={"m": FakeEngine()})
    wl = synth_workload(5, 8, 4, 100, rate_per_s=50, seed=8)
    session.submit("m", wl, slo_class="rt")
    assert all(r.slo_ms is None for r in wl)      # caller's objects untouched
    assert all(r.slo_ms == 25.0 for r in session._workloads["m"])


def test_report_serializes_without_metrics(smoke_params):
    spec = ServingSpec(endpoints=(EndpointSpec(name="m", arch=ARCH),))
    session = ServingSession()
    session.deploy(spec, engines={"m": FakeEngine()})
    session.submit("m", synth_workload(10, 8, 4, 100, rate_per_s=50, seed=4))
    report = session.run()
    doc = report.to_dict()
    assert "metrics" not in doc["fleet"]
    assert doc["spec"]["router"] == "round_robin"
    assert ServingSpec.from_dict(doc["spec"]) == spec
    import json

    json.loads(report.to_json())   # fully JSON-serializable
