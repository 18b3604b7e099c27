"""GQA attention: chunked online-softmax (flash-style) in pure JAX.

This is the XLA execution path used for training, prefill and the distributed
dry-runs (bounded peak memory regardless of sequence length).  The Pallas TPU
kernels in ``repro.kernels`` implement the same math with explicit VMEM tiling
for the hot paths; ``use_pallas=True`` routes through them (CPU: interpret
mode).

Layouts:
  q        (B, Sq, H, dh)
  k, v     (B, T,  K, dh)        K = kv heads, H = K * G
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pad_to(x, mult: int, axis: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,
    kv_lengths=None,
    block_kv: int = 512,
    scale: Optional[float] = None,
):
    """Chunked flash attention with a FLASH BACKWARD (custom VJP).

    Without the custom VJP, autodiff of the kv-block scan stores every
    block's probability matrix as a scan residual — i.e. the full (Sq, T)
    attention matrix in f32, exactly what flash attention exists to avoid
    (measured: 64 GiB residual stacks per layer on qwen1.5-110b train_4k).
    The backward here recomputes s/p per block from (q, k, v, out, lse).

    q_offset: position of q[0] within the kv timeline (int or (B,) array).
    kv_lengths: optional (B,) valid kv lengths (positions >= length masked).
    window: sliding window width (attend to kv in (q_pos-window, q_pos]).
    scale: the scores' factor; None is dh ** -0.5.
    """
    q_off = jnp.asarray(q_offset)
    has_kv_len = kv_lengths is not None
    kv_len = (
        jnp.asarray(kv_lengths)
        if has_kv_len
        else jnp.zeros((q.shape[0],), jnp.int32)  # unused when has_kv_len=False
    )
    return _attention_vjp(q, k, v, q_off, kv_len, causal, window, block_kv,
                          has_kv_len, scale)


def _mask_for(q_pos, k_pos, kv_len, nk, causal, window, has_kv_len=True):
    """q_pos: (B?, Sq); k_pos: (bk,); kv_len: (B,). -> (B, Sq|1, bk) bool."""
    mask = (k_pos < nk)[None, None, :]
    if has_kv_len:
        mask = mask & (
            k_pos[None, :] < kv_len.astype(jnp.int32)[:, None]
        )[:, None, :]
    qp = q_pos[:, :, None]
    kp = k_pos[None, None, :]
    if causal:
        mask = mask & (kp <= qp)
    if window is not None:
        mask = mask & (kp > qp - window)
    return mask


import functools as _functools  # noqa: E402


@_functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _attention_vjp(q, k, v, q_offset, kv_lengths, causal, window, block_kv,
                   has_kv_len, scale):
    out, _ = _attention_fwd_core(q, k, v, q_offset, kv_lengths, causal,
                                 window, block_kv, has_kv_len, scale)
    return out


def _attention_fwd_rule(q, k, v, q_offset, kv_lengths, causal, window,
                        block_kv, has_kv_len, scale):
    out, lse = _attention_fwd_core(q, k, v, q_offset, kv_lengths, causal,
                                   window, block_kv, has_kv_len, scale)
    return out, (q, k, v, out, lse, q_offset, kv_lengths)


def _attention_bwd_rule(causal, window, block_kv, has_kv_len, scale, res,
                        dout):
    q, k, v, out, lse, q_offset, kv_lengths = res
    # residuals may deliver q_offset as a plain Python int (weak-typed scalar
    # concretized by the VJP machinery); normalize so .ndim/.astype work
    q_offset = jnp.asarray(q_offset)
    B, Sq, H, dh = q.shape
    _, T, K, _ = k.shape
    G = H // K
    scale = dh ** -0.5 if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, Sq, K, G, dh) * scale
    do = dout.astype(jnp.float32).reshape(B, Sq, K, G, dh)
    of = out.astype(jnp.float32).reshape(B, Sq, K, G, dh)
    delta = jnp.sum(do * of, axis=-1)                       # (B,Sq,K,G)

    kp, nk = _pad_to(k, block_kv, axis=1)
    vp, _ = _pad_to(v, block_kv, axis=1)
    Tp = kp.shape[1]
    nblk = Tp // block_kv
    kb = kp.reshape(B, nblk, block_kv, K, dh).swapaxes(0, 1)
    vb = vp.reshape(B, nblk, block_kv, K, dh).swapaxes(0, 1)

    q_pos = jnp.arange(Sq, dtype=jnp.int32)[None, :]
    if q_offset.ndim == 0:
        q_pos = q_pos + q_offset.astype(jnp.int32)
    else:
        q_pos = q_pos + q_offset.astype(jnp.int32)[:, None]

    def body(dq_acc, blk):
        kblk, vblk, iblk = blk
        k_pos = iblk * block_kv + jnp.arange(block_kv, dtype=jnp.int32)
        s = jnp.einsum(
            "bqkgd,btkd->bqkgt", qf.astype(kblk.dtype), kblk,
            preferred_element_type=jnp.float32,
        )
        mask = _mask_for(q_pos, k_pos, kv_lengths, nk, causal, window,
                         has_kv_len)
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                      # (B,Sq,K,G,bk)
        p = jnp.where(mask[:, :, None, None, :], p, 0.0)
        dv_blk = jnp.einsum("bqkgt,bqkgd->btkd", p, do)      # (B,bk,K,dh)
        dp = jnp.einsum(
            "bqkgd,btkd->bqkgt", do.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum(
            "bqkgt,btkd->bqkgd", ds.astype(kblk.dtype), kblk,
            preferred_element_type=jnp.float32,
        )
        dk_blk = jnp.einsum("bqkgt,bqkgd->btkd", ds, qf) / scale
        return dq_acc, (dk_blk.astype(k.dtype), dv_blk.astype(v.dtype))

    dq0 = jnp.zeros((B, Sq, K, G, dh), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(nblk, dtype=jnp.int32))
    )
    dk = dks.swapaxes(0, 1).reshape(B, Tp, K, dh)[:, :T]
    dv = dvs.swapaxes(0, 1).reshape(B, Tp, K, dh)[:, :T]
    dq = dq.reshape(B, Sq, H, dh).astype(q.dtype)
    return dq, dk, dv, None, None


_attention_vjp.defvjp(_attention_fwd_rule, _attention_bwd_rule)


def _attention_fwd_core(q, k, v, q_offset, kv_lengths, causal, window,
                        block_kv, has_kv_len=True, scale=None):
    """Returns (out, lse) via the chunked online-softmax forward."""
    q_offset = jnp.asarray(q_offset)
    B, Sq, H, dh = q.shape
    _, T, K, _ = k.shape
    G = H // K
    out_dtype = q.dtype
    scale = dh ** -0.5 if scale is None else scale

    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, K, G, dh)
    k, nk = _pad_to(k, block_kv, axis=1)
    v, _ = _pad_to(v, block_kv, axis=1)
    Tp = k.shape[1]
    nblk = Tp // block_kv

    q_pos = jnp.arange(Sq, dtype=jnp.int32)[None, :]  # (1, Sq)
    if q_offset.ndim == 0:
        q_pos = q_pos + q_offset.astype(jnp.int32)   # (1, Sq)
    else:
        q_pos = q_pos + q_offset.astype(jnp.int32)[:, None]  # (B, Sq)

    kb = k.reshape(B, nblk, block_kv, K, dh).swapaxes(0, 1)
    vb = v.reshape(B, nblk, block_kv, K, dh).swapaxes(0, 1)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, iblk = blk
        k_pos = iblk * block_kv + jnp.arange(block_kv, dtype=jnp.int32)  # (bk,)
        # contract in the cache dtype with f32 accumulation: no f32
        # materialization of kv blocks (keeps the HBM roofline term honest)
        s = jnp.einsum(
            "bqkgd,btkd->bqkgt", qf.astype(kblk.dtype), kblk,
            preferred_element_type=jnp.float32,
        )  # (B, Sq, K, G, bk)
        mask = _mask_for(q_pos, k_pos, kv_lengths, nk, causal, window,
                         has_kv_len)
        mask = mask[:, :, None, None, :]  # (B, Sq, 1, 1, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqkgt,btkd->bqkgd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, K, G, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(nblk, dtype=jnp.int32))
    )
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-20))            # (B, Sq, K, G)
    return out.reshape(B, Sq, H, dh).astype(out_dtype), lse


def attention_reference(q, k, v, *, causal=True, window=None, q_offset=0,
                        kv_lengths=None, scale=None):
    """O(S^2)-memory oracle for tests."""
    B, Sq, H, dh = q.shape
    _, T, K, _ = k.shape
    G = H // K
    scale = dh ** -0.5 if scale is None else scale
    qf = q.astype(jnp.float32).reshape(B, Sq, K, G, dh) * scale
    s = jnp.einsum("bqkgd,btkd->bqkgt", qf, k.astype(jnp.float32))
    q_pos = jnp.arange(Sq)[None, :] + (
        q_offset if isinstance(q_offset, (int, float)) else q_offset[:, None]
    )
    k_pos = jnp.arange(T)
    mask = jnp.ones((1, Sq, T), bool) if not causal else (
        k_pos[None, None, :] <= q_pos[:, :, None]
    )
    if window is not None:
        mask = mask & (k_pos[None, None, :] > q_pos[:, :, None] - window)
    if kv_lengths is not None:
        mask = mask & (k_pos[None, None, :] < kv_lengths[:, None, None])
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgt,btkd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


def decode_attention(
    q,
    k_cache,
    v_cache,
    lengths,
    *,
    window: Optional[int] = None,
    k_new=None,
    v_new=None,
    block_kv: int = 1024,  # kept for API compat; direct path ignores it
    scale: Optional[float] = None,
):
    """Single-token attention over a KV cache.

    q: (B, H, dh); k_cache/v_cache: (B, S, K, dh), only read; lengths: (B,)
    — number of valid cache entries.  The query sits at position
    ``lengths``; ``window`` keeps the cache entries in (lengths - window,
    lengths).  ``k_new``/``v_new`` ((B, K, dh), in the cache dtype) are the
    query token's own kv: their score and value join the cache's in one
    softmax.  ``scale`` is the scores' factor; None is dh ** -0.5.

    Uses the DIRECT (non-chunked) softmax: the (B, K, G, S) score tensor for
    one query token is small, and the un-chunked einsum lets GSPMD implement
    sequence-sharded caches as split-KV flash-decode (partial softmax stats
    + psum) instead of replicating the cache the way the kv-block scan forces
    it to.  Contractions run in the cache dtype with f32 accumulation.
    """
    B, H, dh = q.shape
    S = k_cache.shape[1]
    K = k_cache.shape[2]
    G = H // K
    scale = dh ** -0.5 if scale is None else scale
    qf = (q.astype(jnp.float32) * scale).astype(k_cache.dtype)
    qf = qf.reshape(B, K, G, dh)
    s = jnp.einsum(
        "bkgd,btkd->bkgt", qf, k_cache, preferred_element_type=jnp.float32
    )  # (B, K, G, S)
    k_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    n = lengths.astype(jnp.int32)[:, None]
    mask = k_pos < n
    if window is not None:
        mask = mask & (k_pos > n - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    if k_new is not None:
        s_new = jnp.einsum("bkgd,bkd->bkg", qf, k_new,
                           preferred_element_type=jnp.float32)[..., None]
        m = jnp.maximum(m, s_new)
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    if k_new is not None:
        p_new = jnp.exp(s_new - m)
        l = l + p_new
    l = jnp.maximum(l, 1e-20)
    o = jnp.einsum(
        "bkgt,btkd->bkgd", (p / l).astype(v_cache.dtype),
        v_cache, preferred_element_type=jnp.float32,
    )
    if k_new is not None:
        o = o + jnp.einsum(
            "bkg,bkd->bkgd", (p_new / l)[..., 0].astype(v_new.dtype), v_new,
            preferred_element_type=jnp.float32,
        )
    return o.reshape(B, H, dh).astype(q.dtype)
