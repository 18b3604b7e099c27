"""One run of one cell: weights, deploy, warm-up, the window, the check.

``run_cell`` is the whole run but the look for a chip, so that the tests can
drive it on the CPU at a small size, with the served path broken underneath.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Callable, Optional

import jax
import numpy as np

from harness import check, trace as tr, traffic as tf
from harness.loop import Driver, count_compiles
from harness.record import Run
from harness.spec import Bench, read_metric
from harness.weights import make_params, seed_key

# the traced slice of a ``--trace 1`` run: the window's last seconds, between
# scheduling events, so that starting the profiler stalls the host late in
# the window and stopping it (which writes the trace) stalls it after
TRACE_S = 5.0


def program_config(cfg: dict):
    """The program's configuration of the served arch, checked against the
    configuration file: a difference is an error, not a silent swap."""
    from repro.configs import get_arch

    pc = get_arch(cfg["serving"]["arch"])
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "num_layers":
                cfg["num_hidden_layers"], "vocab_size": cfg["vocab_size"],
            "mlp": cfg["hidden_act"], "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["norm_eps"], "dtype": cfg["torch_dtype"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "family": "dense", "qkv_bias": False, "qk_norm": False,
            "attn_window": None, "mrope": False}
    got = {k: getattr(pc, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from the file: {bad}")
    return pc


def deploy(cfg: dict, traffic: dict, host_params):
    """``ServingSpec`` -> ``ServingSession.deploy``, one replica, the step
    cache off; returns (session, endpoint spec, deploy seconds)."""
    from repro.serving.api import (AutoscaleSpec, EndpointSpec,
                                   ServingSession, ServingSpec)

    ep = EndpointSpec(
        name="m", arch=cfg["serving"]["arch"], format=cfg["serving"]["format"],
        si="si3_dl_server", policy="continuous_batch",
        max_batch=traffic["slots"], max_seq=traffic["max_seq"],
        step_cache=False,
        autoscale=AutoscaleSpec(enabled=False, max_replicas=1))
    registry = tempfile.mkdtemp(prefix="chipbench-registry-")
    try:
        session = ServingSession(registry_root=registry)
        t0 = time.perf_counter()
        session.deploy(ServingSpec(endpoints=(ep,)), params={"m": host_params})
        dt = time.perf_counter() - t0
    finally:
        shutil.rmtree(registry, ignore_errors=True)
    return session, ep, dt


def replica_core(session, ep):
    """The core the fleet builds for one replica of ``ep``, without the
    router: the endpoint's policy factory, power envelope and admission."""
    from repro.serving.core import SchedulerCore

    fe = session._fleet_endpoint(ep, [])
    return SchedulerCore(fe.engine, fe.policy_factory(), step_cache=None,
                         active_power_w=fe.active_power_w,
                         idle_power_w=fe.idle_power_w,
                         admission=fe.admission)


def warm(driver: Driver, traffic: dict, vocab: int) -> None:
    """Serve every prefill shape of the mix through every slot once, with
    two tokens each: the cell's shapes and no others."""
    buckets = tf.prompt_buckets(traffic)
    slots = traffic["slots"]
    rng = tf.rng_for(0, "warm")
    n = max(slots, len(buckets))
    driver.restart()
    for i in range(n):
        P = buckets[i % len(buckets)]
        driver.offer(tf.Job(i, 0.0, rng.integers(0, vocab, P).astype(
            np.int32), 2))
    while driver.busy():
        driver.event()


class Tracer:
    """Starts and stops the profiler between scheduling events."""

    def __init__(self, directory: str, at: float, length: float):
        self.dir, self.at, self.length = directory, at, length
        self.window = None
        self._t0 = None
        self._ann = None

    def __call__(self, t: float) -> None:
        if self._t0 is None and self.window is None and t >= self.at:
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation(tr.SLICE_SPAN)
            self._ann.__enter__()
            self._t0 = t
        elif self._t0 is not None and t >= self._t0 + self.length:
            self.stop(t)

    def stop(self, t: float) -> None:
        if self._t0 is None:
            return
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.window = (self._t0, t)
        self._t0 = None


class Served:
    """A cell's configuration deployed and warmed: one replica of the served
    path, driven on the wall clock, ready for a window."""

    def __init__(self, bench: Bench, cell_name: str, seed: int, *,
                 breaker: Optional[Callable] = None, log=print):
        from repro.models.transformer import init_params
        from repro.serving.request import Request

        cell = bench.cell(cell_name)
        self.cfg = cfg = bench.config(cell["config"])
        self.traffic = traffic = bench.traffic(cell["traffic"])
        pcfg = program_config(cfg)
        self.vocab = cfg["vocab_size"]

        # weights: made on the device in one call, then the host checkpoint
        # the deploy uploads (it holds one device copy, the one it serves)
        t0 = time.perf_counter()
        params = make_params(cfg, seed, cfg["torch_dtype"])
        jax.block_until_ready(params)
        log(f"weights made on the device: {time.perf_counter() - t0:.3f} s")
        want = jax.eval_shape(lambda: init_params(pcfg, seed_key(0)))
        if jax.tree.structure(params) != jax.tree.structure(want) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(want))):
            raise ValueError("the benchmark's weights do not match the tree "
                             "the program loads")
        t0 = time.perf_counter()
        host = jax.device_get(params)
        del params
        log(f"weights brought to the host: {time.perf_counter() - t0:.3f} s")
        self.session, ep, self.deploy_s = deploy(cfg, traffic, host)
        del host
        gc.collect()
        log(f"deployed: {self.deploy_s:.3f} s")
        self.core = replica_core(self.session, ep)
        self.driver = Driver(self.core, Request)
        if breaker is not None:
            breaker(self.core.engine, self.core.policy)
        t0 = time.perf_counter()
        with count_compiles() as compiles:
            warm(self.driver, traffic, self.vocab)
        log(f"warmed: {time.perf_counter() - t0:.3f} s, "
            f"{len(compiles)} compiles")

    def serve(self, seed: int, seconds: float, traffic: Optional[dict] = None,
              tracer: Optional["Tracer"] = None):
        """One window of the mix from ``seed``; returns (window, end, the
        compilations inside it)."""
        traffic = traffic or self.traffic
        d = self.driver
        hook = tracer if tracer is not None else (lambda t: None)
        jobs = tf.open_loop(traffic, seed, seconds, self.vocab)
        d.restart()
        with count_compiles() as compiles:
            end = d.run(jobs, seconds, traffic["drain_cap_s"], hook)
            if tracer is not None:
                tracer.stop(d.now())
        return (0.0, seconds), end, len(compiles)

    def finished(self, window):
        """(rid, prompt, served tokens) of each request due in the window
        that finished."""
        d = self.driver
        return [(r.rid, d.prompts[r.rid], np.asarray(r.tokens))
                for r in self.core.responses
                if window[0] <= d.stamps[r.rid].due < window[1]]

    def close(self) -> None:
        """Free the program's state (weights, cache slab) on the device."""
        tap = self.driver.tap
        tap.detach()
        tap.engine = tap.policy = tap._prefill = tap._decode = None
        self.driver.core = self.driver.policy = None
        self.core = self.session = None
        gc.collect()


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, peaks: dict,
             trace_dir: Optional[str] = None,
             breaker: Optional[Callable] = None,
             on_event: Optional[Callable[[float], None]] = None,
             log=print) -> dict:
    """Returns the result line's fields (without ``device``) and the checks.

    ``breaker(engine, policy)`` breaks the served path underneath (tests);
    ``on_event(t)`` runs after every scheduling event (tests)."""
    limits = bench.limits(cell_name)
    served = Served(bench, cell_name, seed, breaker=breaker, log=log)
    cfg, traffic = served.cfg, served.traffic
    tracer = None
    if trace:
        length = min(TRACE_S, seconds / 2)
        tracer = Tracer(trace_dir, seconds - length, length)
    served.driver.on_event = on_event
    setup_s = time.perf_counter() - t_start
    window, end, compiles = served.serve(seed, seconds, tracer=tracer)
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    d = served.driver
    record = Run(cell_name, cfg, traffic, peaks, window, end, dict(d.stamps),
                 list(d.tap.calls), setup_s, served.deploy_s)
    finished = served.finished(window)
    # free the program's state before the reference runs
    served.close()

    t0 = time.perf_counter()
    got = check.compare(cfg, seed, traffic, finished)
    log(f"reference check: {time.perf_counter() - t0:.3f} s, {got}")
    checks = {k: [got.get(k, float("inf")), v["limit"]]
              for k, v in limits.items()}
    checks["compiles_in_window"] = [compiles, 0]
    checks["requests_failed"] = [len(record.failed()), 0]
    correct = all(v <= lim for v, lim in checks.values()) and \
        got.get("sampled", 0) > 0

    if tracer is not None and tracer.window is not None:
        t0 = time.perf_counter()
        evs = tr.load(trace_dir)
        log(f"trace read: {len(evs)} events, {time.perf_counter() - t0:.3f} s")
        record.trace = tr.summarize(evs)
        record.trace_window = tracer.window
    metrics = {}
    for m in bench.metrics(cell_name, trace):
        value = read_metric(bench.reader(m["name"]), record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(record.due_in_window()),
           "failed": len(record.failed()), "metrics": metrics,
           "memory_peak_bytes": peak,
           "info": {"sampled": got.get("sampled", 0),
                    "sampled_tokens": got.get("tokens", 0),
                    "logit_gap": got.get("logit_gap"),
                    "mean_logit_gap": got.get("mean_logit_gap"),
                    "mean_sq_logit_gap": got.get("mean_sq_logit_gap"),
                    "argmax_share": got.get("argmax_share"),
                    "requests_offered": len(record.stamps)},
           "checks": {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}}
    if record.trace is not None:
        out["trace"] = {"busy_s": record.trace.busy_s,
                        "window_s": record.trace.window_s,
                        "breakdown": tr.breakdown(record.trace)}
    out["record"] = record
    return out
