#!/usr/bin/env python3
"""Serve minitron-4b at its published widths on one TPU chip, through the
normal serving path, and check what comes out.

    python3 chip_smoke.py

Random bf16 weights come from a fixed seed: made on the device under jit,
brought to the host as a checkpoint, then deployed.  Each phase deploys one
endpoint through ``ServingSpec`` -> ``ServingSession.deploy`` -> fleet ->
``SchedulerCore`` -> ``ContinuousBatchPolicy`` -> ``CompiledEngine``, with
the step-time cache off, so every step runs the model:

  * ``rsm``      — bf16 weights, as stored;
  * ``rsm_int8`` — int8 weights served through the Pallas ``int8_matmul``
                   kernel, which must appear in the decode step's HLO.

Each phase serves its requests twice (the first pass compiles the small
eager ops of slot admission), checks that every request got all of its
tokens, and scores the served tokens against a teacher-forced
``transformer.forward`` over prompt plus output.  One JSON line per phase
gives its times and device memory; the timings are host-clock times of
steps that end in ``block_until_ready``, laid on the serving virtual clock.
No J/token is printed: serving still bills at the host's assumed power.

The last line of output is ``{"ok": true, "device": {...}}``.  Without a TPU
the script prints no result and exits non-zero: it has no CPU path.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.energy.hw import chip_spec  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import forward, init_cache, random_checkpoint  # noqa: E402
from repro.serving.api import (  # noqa: E402
    AutoscaleSpec,
    EndpointSpec,
    ServingSession,
    ServingSpec,
)
from repro.serving.request import synth_workload  # noqa: E402

ARCH = "minitron-4b"
SEED = 0
# 8 requests of 16 prompt + 16 new tokens into 4 slots of 256 positions;
# prompts fill their power-of-two bucket, so no padding enters the context
REQUESTS, PROMPT_LEN, MAX_NEW, SLOTS, MAX_SEQ = 8, 16, 16, 4, 256
RATE_PER_S = 16.0
# a served token's logit may sit this fraction of the row's largest |logit|
# below the row maximum: bf16 keeps 8 bits of mantissa, and the decode path
# (cache, one token at a time) and forward (whole sequence) round apart
# through 32 layers.  A wrong token lands several logits below the maximum.
TF_TOL = 2.0 ** -4


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


@contextlib.contextmanager
def _count_compiles():
    """Backend compilations inside the block (a warm window has none)."""
    seen = []

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _memory(key: str):
    stats = jax.devices()[0].memory_stats()      # None on the CPU backend
    return None if stats is None else int(stats[key])


def teacher_forced(cfg, params, prompts, outputs) -> dict:
    """Score served tokens against ``forward`` over prompt + output.

    Returns the largest gap between a row's maximum logit and the served
    token's logit (as a fraction of the row's largest |logit|), and the
    share of served tokens that are the row's argmax.
    """
    P = prompts.shape[1]
    seqs = jnp.asarray(np.concatenate([prompts, outputs], axis=1), jnp.int32)

    @jax.jit
    def score(params, seqs):
        logits = forward(params, cfg, {"tokens": seqs})["logits"]
        logits = logits[:, P - 1:-1]             # the rows that chose outputs
        served = jnp.take_along_axis(logits, seqs[:, P:, None], -1)[..., 0]
        gap = logits.max(-1) - served
        return gap / jnp.abs(logits).max(-1), gap == 0

    rel_gap, exact = jax.device_get(score(params, seqs))
    return {"tf_max_rel_gap": float(rel_gap.max()),
            "tf_argmax_share": float(exact.mean())}


def serve_phase(arch: str, fmt: str, params) -> dict:
    """Deploy ``params`` in format ``fmt``, serve, check, and measure."""
    cfg = get_arch(arch)
    spec = ServingSpec(endpoints=(EndpointSpec(
        name="m", arch=arch, format=fmt, si="si3_dl_server",
        policy="continuous_batch", max_batch=SLOTS, max_seq=MAX_SEQ,
        step_cache=False,
        autoscale=AutoscaleSpec(enabled=False, max_replicas=1)),))
    out = {"phase": fmt, "arch": arch, "requests": REQUESTS,
           "prompt_len": PROMPT_LEN, "max_new": MAX_NEW, "slots": SLOTS,
           "max_seq": MAX_SEQ}

    session = ServingSession()
    t0 = time.perf_counter()
    session.deploy(spec, params={"m": params})
    out["deploy_s"] = time.perf_counter() - t0
    engine = session.engine("m")
    out["weight_bytes"] = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    out["bytes_in_use_after_deploy"] = _memory("bytes_in_use")
    out["compile_s"] = (engine.warmup(1, PROMPT_LEN)
                        + engine.warmup(SLOTS, PROMPT_LEN))

    wl = synth_workload(REQUESTS, PROMPT_LEN, MAX_NEW, cfg.vocab_size,
                        rate_per_s=RATE_PER_S, seed=SEED)
    prompts = np.stack([r.prompt for r in wl])
    gaps = []
    for rnd in ("cold", "warm"):
        with _count_compiles() as compiles:
            t0 = time.perf_counter()
            metrics = session.serve({"m": wl}).endpoints["m"].metrics
            out[f"serve_{rnd}_s"] = time.perf_counter() - t0
        out[f"compiles_{rnd}"] = len(compiles)
        by_rid = {r.rid: r for r in metrics.responses}
        _require(sorted(by_rid) == [r.rid for r in wl],
                 f"{fmt}: served {len(by_rid)} of {REQUESTS} requests")
        outputs = np.stack([np.asarray(by_rid[r.rid].tokens) for r in wl])
        _require(outputs.shape == (REQUESTS, MAX_NEW),
                 f"{fmt}: output tokens {outputs.shape}, expected "
                 f"{(REQUESTS, MAX_NEW)}")
        t0 = time.perf_counter()
        tf = teacher_forced(cfg, engine.params, prompts, outputs)
        out[f"check_{rnd}_s"] = time.perf_counter() - t0
        _require(tf["tf_max_rel_gap"] <= TF_TOL,
                 f"{fmt} ({rnd}): a served token's logit is "
                 f"{tf['tf_max_rel_gap']:.4f} of the row's |logit| below "
                 f"its maximum (tolerance {TF_TOL})")
        gaps.append(tf)
    out["tf_tol"] = TF_TOL
    out["tf_max_rel_gap"] = max(g["tf_max_rel_gap"] for g in gaps)
    out["tf_argmax_share"] = min(g["tf_argmax_share"] for g in gaps)

    rs = metrics.responses                       # the warm round
    out["ttft_p50_s"] = float(np.median([r.ttft_s for r in rs]))
    out["itl_p50_s"] = float(np.median(
        [(r.done_s - r.first_token_s) / (MAX_NEW - 1) for r in rs]))
    out["output_tok_s"] = metrics.throughput_tok_s

    cache = jax.eval_shape(lambda: init_cache(cfg, SLOTS, MAX_SEQ))
    tok = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    hlo = engine._decode_jit.lower(engine.params, cache, tok).as_text()
    out["tpu_custom_call"] = "tpu_custom_call" in hlo
    out["peak_bytes_in_use"] = _memory("peak_bytes_in_use")
    return out


def run() -> list:
    """Both phases on one host checkpoint; each phase's device memory is
    released before the next deploys."""
    t0 = time.perf_counter()
    params = random_checkpoint(get_arch(ARCH), SEED)
    init_s = time.perf_counter() - t0
    results = []
    for fmt in ("rsm", "rsm_int8"):
        res = serve_phase(ARCH, fmt, params)
        res["init_s"] = init_s
        gc.collect()                  # the phase's session, engine, registry
        results.append(res)
        print(json.dumps(res), flush=True)
    return results


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU attached (JAX found {dev.platform!r}); "
              "this script has no CPU path", file=sys.stderr)
        return 1
    hbm = chip_spec(dev.device_kind).hbm_bytes    # raises for an unknown chip
    print(f"cache: {use_compile_cache()}", flush=True)
    for res in run():
        name = res["phase"]
        _require(name != "rsm_int8" or res["tpu_custom_call"],
                 "rsm_int8: no tpu_custom_call in the decode step's HLO")
        _require(res["peak_bytes_in_use"] < hbm,
                 f"{name}: peak {res['peak_bytes_in_use']} B >= HBM {hbm} B")
        # one copy of the weights: after the deploy the device holds the
        # served weights and little else (no template, no earlier phase)
        extra = res["bytes_in_use_after_deploy"] - res["weight_bytes"]
        _require(extra < 0.25 * res["weight_bytes"],
                 f"{name}: {extra} B on the device beyond the served weights")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
