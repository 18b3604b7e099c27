"""granite-4.0-h-micro: the architecture module
(``chipbench/arch/granite_hybrid.py``) against the program at published
sizes (weights tree, counts) and at a tiny size on the CPU (logits against
the plain reference through prefill and decode, the counters the served
path keeps, a whole cell run)."""

import dataclasses
import json
import time

import chipbench_fixtures as fx
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import arch, cell, weights as W
from harness.cell import run_cell
from harness.peaks import PEAKS
from harness.spec import Bench, load_reader
from repro.configs import get_arch
from repro.models import decode_step, init_params, prefill, transformer

CFG = json.loads((fx.CB / "configs" / "granite4-h-micro.json").read_text())
A = arch.of(CFG)
SEED = 2**31 + 7

# granite's shapes at the program's smoke size: one period of one Mamba and
# one attention layer, chunks of 8
TINY = dict(CFG, name="tiny-granite", hidden_size=256,
            num_attention_heads=4, num_key_value_heads=1, head_dim=32,
            num_hidden_layers=2, layer_types=["mamba", "attention"],
            vocab_size=1024, intermediate_size=512,
            shared_intermediate_size=512, mamba_n_heads=16, mamba_d_head=32,
            mamba_d_state=16, mamba_chunk_size=8, torch_dtype="float32",
            weights="float32",
            serving={"arch": "granite-4.0-h-micro-smoke", "format": "rsm"})


def _shapes(tree):
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def test_the_weights_are_the_tree_the_program_loads_at_published_sizes():
    pcfg = cell.program_config(CFG)          # the fields the file implies
    assert pcfg.name == "granite-4.0-h-micro" and pcfg.num_layers == 40
    d = A.dims(CFG)
    assert (d["Lm"], d["La"]) == (36, 4)
    assert [A.layer_at(d, l)[0] for l in (4, 5, 6, 15, 35, 39)] == \
        ["mamba", "attention", "mamba", "attention", "attention", "mamba"]
    assert A.layer_at(d, 15)[1] == {"L": 15, "La": 1}
    assert A.layer_at(d, 16)[1] == {"L": 16, "Lm": 14}
    made = jax.eval_shape(lambda: W.make_params(CFG, 0, "bfloat16"))
    want = jax.eval_shape(lambda: init_params(pcfg, jax.random.PRNGKey(0)))
    assert _shapes(made) == _shapes(want)


def test_parameter_count_at_published_sizes():
    n = A.total_params(CFG)
    leaves = jax.eval_shape(lambda: W.make_params(CFG, 0, "bfloat16"))
    assert n == sum(a.size for a in jax.tree.leaves(leaves))
    assert n == get_arch("granite-4.0-h-micro").param_count()
    assert 3.18e9 < n < 3.20e9                # "3B": 3.19 B, 6.38 GB bf16
    assert A.served_weight_bytes(CFG, "bfloat16") == 2 * n


def test_decode_bytes_against_a_hand_count():
    # K/V: 2 (k, v) x 4 attention layers x 8 heads x 64 x 2 bytes;
    # state: 36 Mamba layers x (64 heads x 64 x 128 x 4 bytes of SSM state
    # + 3 x 4352 conv inputs x 2 bytes)
    kv, state = 8192, 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert A.kv_bytes_per_position(CFG) == kv
    assert A.state_bytes_per_slot(CFG) == state == 76_437_504
    lengths = (0, 99, 4095)
    want = 2 * A.total_params(CFG) + (1 + 100 + 4096) * kv + 2 * 3 * state
    assert A.decode_bytes(CFG, lengths, "bfloat16") == want
    # 32 slots of 4096: 2.45 GB of state, 1.07 GB of K/V
    assert (32 * state, 32 * 4096 * kv) == (2_446_000_128, 2**30)


def test_the_programs_cache_holds_what_the_module_counts():
    pcfg = get_arch("granite-4.0-h-micro")
    assert transformer.cache_bytes(pcfg, 4096) == (
        A.kv_bytes_per_position(CFG), A.state_bytes_per_slot(CFG))
    cache = jax.eval_shape(lambda: transformer.init_cache(pcfg, 32, 4096))
    nbytes = {k: a.size * a.dtype.itemsize for k, a in cache.items()}
    assert nbytes["ssm"] + nbytes["conv"] == 32 * A.state_bytes_per_slot(CFG)
    assert nbytes["k"] + nbytes["v"] == 32 * 4096 * 8192


def _periods_cfg(dtype):
    """Two periods of (Mamba, Mamba, attention), at the tiny widths."""
    js = dict(TINY, num_hidden_layers=6, torch_dtype=dtype, weights=dtype,
              layer_types=["mamba", "mamba", "attention"] * 2)
    pcfg = dataclasses.replace(get_arch("granite-4.0-h-micro-smoke"),
                               num_layers=6, dtype=dtype,
                               layer_pattern=("mamba", "mamba", "attention"))
    assert A.program_fields(js) == {
        k: getattr(pcfg, k) for k in A.program_fields(js)}
    return js, pcfg


def _reference_logits(js, tokens):
    """The module's plain forward pass over the whole sequence, float32."""
    d = A.dims(js)
    with jax.default_matmul_precision("highest"):
        h = A.embed(d, W.table(js, SEED, "embed", js["torch_dtype"]), tokens)
        for l in range(A.num_layers(d)):
            w = jax.tree.map(lambda a: a.astype(jnp.float32),
                             W.layer_params(js, SEED, l, js["torch_dtype"]))
            h = A.LAYERS[A.layer_at(d, l)[0]](d, h, w)
        x = A.final(d, h, W.table(js, SEED, "final_norm", js["torch_dtype"]))
        table = W.table(js, SEED, "embed", js["torch_dtype"])
        return A.logits(d, x, table.astype(jnp.float32))


# max |program - reference| over the logits' standard deviation: float32
# only rounds (the chunked scan sums in another order; read 9.4e-6, so 10x
# room); bfloat16 rounds the weights, activations and residual stream to 8
# bits (read 0.106, 3x room)
TOL = {"float32": 1e-4, "bfloat16": 0.3}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_match_the_reference(dtype):
    """A 21-token prompt (two chunk boundaries and a ragged tail), then six
    tokens through the cache, against the reference's full forward pass."""
    js, pcfg = _periods_cfg(dtype)
    params = W.make_params(js, SEED, dtype)
    P, n = 21, 6
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, P + n), 0,
                                js["vocab_size"])
    ref = _reference_logits(js, tokens)
    logits, cache = prefill(params, pcfg, {"tokens": tokens[:, :P]}, 64)
    got = [logits]
    step = jax.jit(lambda c, t: decode_step(params, pcfg, c, t))
    for i in range(P, P + n - 1):
        logits, cache = step(cache, tokens[:, i])
        got.append(logits)
    got = jnp.stack(got, 1).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref[:, P - 1:P + n - 1])))
    assert err < TOL[dtype] * float(jnp.std(ref)), err


def test_state_bytes_inserted_match_the_modules_count():
    """Five admissions through the served path copy five slots' recurrent
    state, as the module counts a slot's; the engine records both counts."""
    from repro.core.engines import CompiledEngine
    from repro.serving.core import SchedulerCore
    from repro.serving.request import Request
    from repro.serving.scheduler import ContinuousBatchPolicy

    pcfg = cell.program_config(TINY)
    engine = CompiledEngine(pcfg, W.make_params(TINY, SEED, "float32"), 64)
    assert engine.state_bytes_per_slot == A.state_bytes_per_slot(TINY)
    assert engine.kv_bytes_per_token == A.kv_bytes_per_position(TINY, 4)
    core = SchedulerCore(engine, ContinuousBatchPolicy(3, 64))
    rs = np.random.RandomState(0)
    core.run([Request(rid=i, prompt=rs.randint(0, 1024, 8).astype(np.int32),
                      max_new_tokens=3, arrival_s=0.0) for i in range(5)])
    assert core.counters["admissions"] == 5
    assert core.counters["state_bytes_inserted"] == \
        5 * A.state_bytes_per_slot(TINY)


@pytest.fixture(scope="module")
def granite_bench(tmp_path_factory):
    return Bench(fx.make_checkout(tmp_path_factory.mktemp("co"),
                                  {"mean_logit_gap": 1e-3}, config=TINY))


def test_a_tiny_granite_cell_is_correct(granite_bench):
    out = run_cell(granite_bench, "tiny.chat", SEED, 1.5, False,
                   t_start=time.perf_counter(), peaks=PEAKS["TPU v5 lite"],
                   log=lambda *a: None)
    out.pop("record")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_the_state_insert_reader(monkeypatch):
    from harness import host, trace as tr
    from harness.record import Run

    h = lambda name, t0, dur: tr.Ev("/host:CPU", "python3", name,  # noqa
                                    t0 * 1e6, dur * 1e6)
    evs = [h("cb.slice", 0, 100), h("serve.insert", 10, 9),
           h("serve.insert.kv", 10, 2), h("serve.insert.state", 12, 6),
           h("serve.insert.state", 40, 4), h("serve.insert.state", 98, 5)]
    run = Run("x", {}, {}, {}, (0.0, 1.0), 1.0, {}, [], 0.0, 0.0)
    read = load_reader(fx.CB / "metrics", "state_insert_ms.chat32").read
    monkeypatch.setattr(host, "events", lambda run: evs)
    assert read(run) == pytest.approx(5.0)     # the last is cut by the edge
    monkeypatch.setattr(host, "events", lambda run: evs[:3])
    assert read(run) is None                   # a program without the span
