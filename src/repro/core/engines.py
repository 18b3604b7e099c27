"""SI1/SI2 execution engines.

SI1 ``EagerEngine`` — the paper's 'No runtime engine': the framework executes
the model op-by-op (``jax.disable_jit``), exactly like calling TF/PyTorch
directly behind a hand-built API.  Simple, zero compile latency, no graph
optimization.

SI2 ``CompiledEngine`` — the paper's 'Runtime engine' (ONNX-RT / TensorRT /
torch.jit analogue): the model is lowered and AOT-compiled by XLA at load
time; inference runs the optimized executable.  Optionally consumes the TD2
``rsm_int8`` optimized format (weight-only int8 with fused dequant — see
``repro.kernels.int8_matmul``).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.models import transformer
from repro.serving.telemetry.host import span


def token_landing_s(prefill_s: float, decode_s: float, n_steps: int,
                    n: int) -> float:
    """Offset from generation start at which the n-th token (1-based) lands.

    Token 1 comes out of the prefill logits; tokens 2..n_steps land one
    decode step apart (``decode_s`` spans the ``n_steps - 1`` decode calls).
    Schedulers use this to retire each request in a batch at the step where
    *its* last token lands instead of billing everyone for the longest
    request's decode.
    """
    step = decode_s / max(n_steps - 1, 1)
    return prefill_s + max(min(n, n_steps) - 1, 0) * step


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, n_new)
    prefill_s: float
    decode_s: float               # total decode wall time
    n_steps: int

    @property
    def decode_s_per_token(self) -> float:
        return self.decode_s / max(self.n_steps, 1)

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s

    def token_done_s(self, n: int) -> float:
        """Landing offset of this result's n-th token (see token_landing_s)."""
        return token_landing_s(self.prefill_s, self.decode_s, self.n_steps, n)


class Engine:
    """Shared generation loop; subclasses choose the execution mode."""

    name = "abstract"

    def __init__(self, cfg: ModelConfig, params, max_seq: int = 256):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq

    # -- execution hooks ------------------------------------------------------
    def _prefill(self, tokens):
        raise NotImplementedError

    def _decode(self, cache, tokens):
        raise NotImplementedError

    def warmup(self, batch: int, prompt_len: int) -> float:
        return 0.0

    # -- public API -----------------------------------------------------------
    def generate(self, tokens: np.ndarray, max_new_tokens: int) -> GenerationResult:
        """Greedy generation. tokens: (B, S) int32."""
        tokens = jnp.asarray(tokens, jnp.int32)
        t0 = time.perf_counter()
        logits, cache = self._prefill(tokens)
        logits.block_until_ready()
        t1 = time.perf_counter()
        out = []
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
        for _ in range(max_new_tokens - 1):
            logits, cache = self._decode(cache, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(tok)
        tok.block_until_ready()
        t2 = time.perf_counter()
        return GenerationResult(
            tokens=np.stack([np.asarray(t) for t in out], axis=1),
            prefill_s=t1 - t0,
            decode_s=t2 - t1,
            n_steps=max_new_tokens,
        )

    # serving hooks for continuous batching (SI3) ------------------------------
    def prefill_one(self, tokens):
        """tokens: (1, S). Returns (logits (1,V), cache_B1)."""
        return self._prefill(jnp.asarray(tokens, jnp.int32))

    def decode_batch(self, cache, tokens):
        return self._decode(cache, jnp.asarray(tokens, jnp.int32))

    def forward_scores(self, batch):
        raise NotImplementedError


class EagerEngine(Engine):
    """SI1: no runtime engine — op-by-op framework dispatch."""

    name = "SI1_eager"

    def _extra_inputs(self, B, S):
        batch = {}
        cfg = self.cfg
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                        cfg.jnp_dtype)
        return batch

    def _prefill(self, tokens):
        with jax.disable_jit():
            batch = {"tokens": tokens, **self._extra_inputs(*tokens.shape)}
            return transformer.prefill(self.params, self.cfg, batch, self.max_seq)

    def _decode(self, cache, tokens):
        with jax.disable_jit():
            return transformer.decode_step(self.params, self.cfg, cache, tokens)


class CompiledEngine(Engine):
    """SI2: runtime engine — XLA AOT-compiled executables per shape."""

    name = "SI2_compiled"

    def __init__(self, cfg: ModelConfig, params, max_seq: int = 256,
                 donate_cache: bool = True):
        super().__init__(cfg, params, max_seq)
        # (entry, input shape) -> calls; a key's first call compiles (or
        # loads from the persistent cache), inside a ``serve.compile`` span
        self._compiled: Dict[Tuple[str, tuple], int] = {}

        def prefill_fn(params, batch):
            return transformer.prefill(params, cfg, batch, max_seq)

        def decode_fn(params, cache, tokens):
            return transformer.decode_step(params, cfg, cache, tokens)

        self._prefill_jit = jax.jit(prefill_fn)
        self._decode_jit = (
            jax.jit(decode_fn, donate_argnums=(1,))
            if donate_cache
            else jax.jit(decode_fn)
        )

    def _extra_inputs(self, B, S):
        batch = {}
        cfg = self.cfg
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                        cfg.jnp_dtype)
        return batch

    def _call(self, entry: str, shape: tuple, fn, *args):
        key = (entry, tuple(shape))
        n = self._compiled.get(key, 0)
        self._compiled[key] = n + 1
        if n:
            return fn(*args)
        with span("serve.compile", entry=entry, shape=str(key[1])):
            return fn(*args)

    def _prefill(self, tokens):
        batch = {"tokens": tokens, **self._extra_inputs(*tokens.shape)}
        return self._call("prefill", tokens.shape, self._prefill_jit,
                          self.params, batch)

    def _decode(self, cache, tokens):
        return self._call("decode", tokens.shape, self._decode_jit,
                          self.params, cache, tokens)

    def warmup(self, batch: int, prompt_len: int) -> float:
        """AOT-compile the (batch, prompt_len) shapes; returns compile seconds.

        This is the 'runtime engine' load/optimization step the paper
        attributes to SI2 (cf. TensorRT engine build / ONNX session init).
        """
        t0 = time.perf_counter()
        tokens = jnp.zeros((batch, prompt_len), jnp.int32)
        logits, cache = self._prefill(tokens)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        self._decode(cache, tok)[0].block_until_ready()
        return time.perf_counter() - t0


def make_engine(si_name: str, cfg, params, max_seq: int = 256) -> Engine:
    if si_name in ("si1_no_runtime", "SI1"):
        return EagerEngine(cfg, params, max_seq)
    return CompiledEngine(cfg, params, max_seq)
