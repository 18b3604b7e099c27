"""Pallas TPU decode attention: one query token vs a long KV cache.

The hot loop of ``decode_32k`` / ``long_500k``: for each (batch, kv-head) the
G=H/K query rows of the GQA group attend over the cache, streamed through VMEM
``block_s`` keys at a time with a flash-style running (m, l, acc).  Per-request
valid ``lengths`` and an optional sliding window bound the scan.

Layouts: q (B, K, G, dh); k/v cache (B, K, S, dh); lengths (B,) int32, held
whole in SMEM (a (1, 1) VMEM block is below the TPU's (8, 128) tiling).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, window, block_s: int):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]                  # valid cache entries
    q = q_ref[0, 0].astype(jnp.float32) * scale         # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bs, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                    # (G, bs)
    k_pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < length
    if window is not None:
        mask &= k_pos > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v_ref[0, 0].astype(jnp.float32), preferred_element_type=jnp.float32
    )

    @pl.when(si == ns - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)
        ).astype(o_ref.dtype)


def decode_attention(
    q, k_cache, v_cache, lengths, *, window=None, block_s: int = 512,
    interpret: bool = False,
):
    """q: (B, K, G, dh); caches: (B, K, S, dh); lengths: (B,) incl. current.

    Returns (B, K, G, dh).
    """
    B, K, G, dh = q.shape
    S = k_cache.shape[2]
    block_s = min(block_s, S)
    ns = pl.cdiv(S, block_s)
    kernel = functools.partial(
        _kernel, scale=dh ** -0.5, window=window, block_s=block_s
    )
    return pl.pallas_call(
        kernel,
        grid=(B, K, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, dh), lambda b, k, si: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, block_s, dh), lambda b, k, si: (b, k, si, 0)),
            pl.BlockSpec((1, 1, block_s, dh), lambda b, k, si: (b, k, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, k, si: (b, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.reshape(B).astype(jnp.int32), q, k_cache, v_cache)
