"""Serving-layer integration: engines, schedulers, server, cloud, formats."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.add import (
    Deployment,
    ModelFormat,
    RequestProcessing,
    ServingInfrastructure,
)
from repro.core.engines import CompiledEngine, EagerEngine
from repro.models import init_params
from repro.serving import formats
from repro.serving.cloud import CloudService
from repro.serving.container import generate_artifact, overhead
from repro.serving.request import Request, synth_workload
from repro.serving.scheduler import (
    ContinuousBatchScheduler,
    DynamicBatchScheduler,
    RealTimeScheduler,
)
from repro.serving.server import ModelPackage, ServingServer

ARCH = "yi-9b-smoke"


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engines_agree(setup):
    """SI1 (eager) and SI2 (compiled) produce identical greedy tokens."""
    cfg, params = setup
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                              (2, 8)).astype(np.int32)
    e1 = EagerEngine(cfg, params, max_seq=32)
    e2 = CompiledEngine(cfg, params, max_seq=32)
    r1 = e1.generate(tokens, 4)
    r2 = e2.generate(tokens, 4)
    np.testing.assert_array_equal(r1.tokens, r2.tokens)


def test_compiled_warmup_amortizes(setup):
    cfg, params = setup
    e = CompiledEngine(cfg, params, max_seq=32)
    compile_s = e.warmup(1, 8)
    tokens = np.zeros((1, 8), np.int32)
    r = e.generate(tokens, 4)
    assert compile_s > r.prefill_s + r.decode_s  # runtime-engine build >> run


@pytest.mark.parametrize("sched_cls", [RealTimeScheduler,
                                       DynamicBatchScheduler,
                                       ContinuousBatchScheduler])
def test_schedulers_complete_all(setup, sched_cls):
    cfg, params = setup
    engine = CompiledEngine(cfg, params, max_seq=64)
    wl = synth_workload(5, 8, 3, cfg.vocab_size, rate_per_s=100, seed=1)
    if sched_cls is RealTimeScheduler:
        sched = sched_cls(engine)
    elif sched_cls is DynamicBatchScheduler:
        sched = sched_cls(engine, max_batch=4, timeout_ms=10)
    else:
        sched = sched_cls(engine, num_slots=4, max_seq=64)
    m = sched.run(wl)
    assert len(m.responses) == 5
    assert all(len(r.tokens) == 3 for r in m.responses)
    assert m.total_tokens == 15
    for r in m.responses:
        assert r.done_s >= r.first_token_s >= r.start_s - 1e-9
        assert r.start_s >= r.arrival_s - 1e-9


def test_continuous_batching_matches_realtime_tokens(setup):
    """Batching must not change greedy outputs (order-independence)."""
    cfg, params = setup
    engine = CompiledEngine(cfg, params, max_seq=64)
    wl = synth_workload(4, 8, 3, cfg.vocab_size, rate_per_s=1000, seed=3)
    rt = RealTimeScheduler(engine).run(wl)
    cb = ContinuousBatchScheduler(engine, num_slots=2, max_seq=64).run(wl)
    rt_by_id = {r.rid: r.tokens for r in rt.responses}
    cb_by_id = {r.rid: r.tokens for r in cb.responses}
    for rid in rt_by_id:
        np.testing.assert_array_equal(rt_by_id[rid], cb_by_id[rid])


def test_server_wire_roundtrip(setup):
    cfg, params = setup
    dep = Deployment(arch=ARCH, si=ServingInfrastructure.SI3_DL_SERVER,
                     request_processing=RequestProcessing.DYNAMIC_BATCH,
                     max_batch=4, max_seq=64)
    srv = ServingServer(dep)
    url = srv.register(ModelPackage(name="m", arch=ARCH, params=params,
                                    max_seq=64))
    assert url == "/v1/models/m:predict"
    wl = synth_workload(3, 8, 2, cfg.vocab_size, rate_per_s=100, seed=2)
    wire = [
        (r.arrival_s,
         srv.codec.encode_request(r.rid, r.prompt, r.max_new_tokens))
        for r in wl
    ]
    out, metrics, stats = srv.handle_wire("m", wire)
    assert len(out) == 3
    assert stats.request_bytes > 0 and stats.response_bytes > 0


def test_formats_roundtrip(setup, tmp_path):
    cfg, params = setup
    # native npz
    formats.save_native(params, str(tmp_path / "m"))
    p1 = formats.load_native(params, str(tmp_path / "m"))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    # rsm
    formats.save_rsm(params, str(tmp_path / "rsm"))
    p2 = formats.load_rsm(params, str(tmp_path / "rsm"))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_int8_format_smaller_and_close(setup, tmp_path):
    cfg, params = setup
    full = formats.save_rsm(params, str(tmp_path / "full"), quantize=False)
    q = formats.save_rsm(params, str(tmp_path / "q"), quantize=True)
    assert q < full * 0.75  # int8 format is materially smaller (TD2)
    pq = formats.load_rsm(params, str(tmp_path / "q"))
    # dequantized params are close to the originals
    errs = []
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(pq)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if a.ndim == 2 and a.size:
            denom = np.abs(a).mean() + 1e-9
            errs.append(np.abs(a - b).mean() / denom)
    assert max(errs) < 0.02


def test_int8_qtensor_serving(setup, tmp_path):
    """rsm_int8 + QTensor path generates tokens close to full precision."""
    cfg, params = setup
    formats.save_rsm(params, str(tmp_path / "q"), quantize=True)
    pq = formats.load_rsm(params, str(tmp_path / "q"), as_qtensor=True)
    tokens = np.random.RandomState(1).randint(0, cfg.vocab_size,
                                              (1, 8)).astype(np.int32)
    full_logits, _ = CompiledEngine(cfg, params, 16)._prefill(
        jnp.asarray(tokens))
    q_logits, _ = CompiledEngine(cfg, pq, 16)._prefill(jnp.asarray(tokens))
    corr = np.corrcoef(np.asarray(full_logits).ravel(),
                       np.asarray(q_logits).ravel())[0, 1]
    assert corr > 0.99, corr


def test_rsm_roundtrip_keeps_bf16(setup, tmp_path):
    """bf16 weights go through the registry as bf16: same dtype, same
    values, and two bytes per parameter on disk."""
    cfg, params = setup
    bf16 = jax.tree.map(lambda x: np.asarray(x, jnp.bfloat16), params)
    formats.save_rsm(bf16, str(tmp_path / "rsm"))
    # a shape-only template: every value must come from the file
    back = formats.load_rsm(jax.eval_shape(lambda: bf16), str(tmp_path / "rsm"))
    for a, b in zip(jax.tree.leaves(bf16), jax.tree.leaves(back)):
        assert b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    on_disk = os.path.getsize(tmp_path / "rsm" / "tensors.bin")
    assert on_disk == sum(x.nbytes for x in jax.tree.leaves(bf16))


def test_int8_quantizes_only_dense_weights(tmp_path):
    """With 8+ stacked layers the per-layer norms are (L, D) matrices too;
    only the weights dense() consumes may become QTensor leaves, or the
    layer scan gets mismatched leading axes."""
    import dataclasses

    cfg = dataclasses.replace(get_arch(ARCH), num_layers=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    formats.save_rsm(params, str(tmp_path / "q"), quantize=True)
    pq = formats.load_rsm(params, str(tmp_path / "q"), as_qtensor=True)
    quantized = sorted(
        formats._key(p) for p, leaf in jax.tree_util.tree_flatten_with_path(
            pq, is_leaf=lambda x: isinstance(x, formats.QTensor))[0]
        if isinstance(leaf, formats.QTensor))
    assert quantized == [f"layers/{m}/{w}" for m, w in (
        ("attn", "wk"), ("attn", "wo"), ("attn", "wq"), ("attn", "wv"),
        ("mlp", "wi_gate"), ("mlp", "wi_up"), ("mlp", "wo"))]
    tokens = jnp.asarray(np.arange(8, dtype=np.int32)[None])
    full, _ = CompiledEngine(cfg, params, 16)._prefill(tokens)
    q, _ = CompiledEngine(cfg, pq, 16)._prefill(tokens)
    assert np.corrcoef(np.asarray(full).ravel(),
                       np.asarray(q).ravel())[0, 1] > 0.99


def test_cloud_service(setup, tmp_path):
    cfg, params = setup
    cloud = CloudService(str(tmp_path / "registry"))
    cloud.upload_model("m", 1, params, ModelFormat.RSM)
    dep = Deployment(arch=ARCH, si=ServingInfrastructure.SI4_CLOUD_SERVICE,
                     request_processing=RequestProcessing.DYNAMIC_BATCH,
                     max_batch=4, max_seq=64, min_replicas=1, max_replicas=3)
    url = cloud.deploy("m", 1, dep, template_params=params)
    assert url.startswith("https://")
    wl = synth_workload(6, 8, 2, cfg.vocab_size, rate_per_s=50, seed=4)
    m = cloud.predict("m", wl, service_time_hint_s=0.05)
    assert len(m.responses) == 6
    assert cloud.endpoints["m"]["replicas"] >= 1
    assert cloud.registry.versions("m") == [1]


def test_container_artifacts():
    from repro.core.add import Containerization

    for c in Containerization:
        dep = Deployment(arch=ARCH, containerization=c)
        art = generate_artifact(dep)
        assert isinstance(art, str) and len(art) > 10
        ovh = overhead(c)
        assert ovh.energy_overhead >= 1.0
        assert ovh.simulated
    d = Deployment(arch=ARCH, containerization=Containerization.DOCKER)
    assert "FROM python" in generate_artifact(d)
