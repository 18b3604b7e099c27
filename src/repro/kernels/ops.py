"""Public jit'd wrappers for the Pallas kernels.

On a TPU the kernels lower to Mosaic.  On the CPU, where the tests run, they
execute with ``interpret=True``: the kernel body runs as plain XLA, which
checks results but says nothing about what the TPU compiler accepts or how
fast it runs.  Any other backend is an error, never a silent interpreter.
``interpret`` is resolved from the backend at trace time unless overridden.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_matmul import int8_matmul as _int8
from repro.kernels.int8_matmul import quantize_int8  # noqa: F401 (re-export)
from repro.kernels.moe_gmm import moe_gmm as _gmm
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on tpu or interpreted on cpu; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=128,
                    block_kv=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_kv=block_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, window=None,
                     block_s=512, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _decode(q, k_cache, v_cache, lengths, window=window,
                   block_s=block_s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                             "interpret"))
def moe_gmm(x, w, group_sizes=None, *, block_c=128, block_f=128, block_d=256,
            interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _gmm(x, w, group_sizes, block_c=block_c, block_f=block_f,
                block_d=block_d, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_d",
                                             "interpret"))
def int8_matmul(x, w_q, scales, *, block_m=128, block_n=128, block_d=512,
                interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _int8(x, w_q, scales, block_m=block_m, block_n=block_n,
                 block_d=block_d, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, s0, *, chunk=64, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rwkv6(r, k, v, w, u, s0, chunk=chunk, interpret=interpret)
