"""Plain building blocks that the architecture modules' references compose,
in ``jax.numpy``: float32, every matrix product at ``Precision.HIGHEST``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotary(x, theta: float, fraction: float):
    """Rotary positions on ``fraction`` of each head, rotate-half pairing;
    x: (n, T, heads, hd), positions 0..T-1."""
    hd = x.shape[-1]
    rot = int(round(hd * fraction))
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def causal_attention(q, k, v):
    """Grouped-query causal softmax attention over one sequence: q (T, H,
    hd); k, v (T, K, hd); returns (T, H * hd)."""
    T, H, hd = q.shape
    K = k.shape[1]
    q = q.reshape(T, K, H // K, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI)
    return o.reshape(T, H * hd)

