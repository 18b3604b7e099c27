"""The plain reference: the configuration's forward pass in ``jax.numpy``.

Float32 throughout, every matrix product at ``Precision.HIGHEST``, no cache,
no kernels, no batching tricks; weights regenerated from the seed one layer
at a time (``harness.weights``), so it takes nothing the program made.  The
layer equations are those the configuration file states:

    x = h + attn(rms_norm(h) * ln1),   h' = x + mlp(rms_norm(x) * ln2)
    attn: grouped-query causal softmax attention, rotary positions on
          ``rotary_fraction`` of each head (rotate-half pairing)
    mlp:  relu(x wi)^2 wo
    logits = (rms_norm(h) * final_norm) lm_head

An ``int8`` configuration serves each layer matrix as per-output-channel
symmetric int8 with an f32 scale; the reference quantizes the same bf16
weights itself, by that rule, and computes on the dequantized values.
``bits`` computes a control in a lower precision: the layer matrices
quantized to ``bits`` per weight by the same rule.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W
from harness.ops import dims

HI = jax.lax.Precision.HIGHEST
_MATS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
         ("mlp", "wi"), ("mlp", "wo"))


def quantize(w, bits: int):
    """Per-output-channel symmetric quantization, dequantized to f32."""
    w = w.astype(jnp.float32)
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2), 1e-8) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotary(x, theta: float, fraction: float):
    """x: (n, T, heads, hd); positions 0..T-1."""
    hd = x.shape[-1]
    rot = int(round(hd * fraction))
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def _attend(q, k, v):
    """One sequence: q (T, H, hd); k, v (T, K, hd); causal."""
    T, H, hd = q.shape
    K = k.shape[1]
    q = q.reshape(T, K, H // K, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI)
    return o.reshape(T, H * hd)


def _layer(d, theta, fraction, eps, h, w):
    n, T, _ = h.shape
    x = rms_norm(h, w["ln1"], eps)
    a = w["attn"]
    q = jnp.einsum("ntd,de->nte", x, a["wq"], precision=HI)
    k = jnp.einsum("ntd,de->nte", x, a["wk"], precision=HI)
    v = jnp.einsum("ntd,de->nte", x, a["wv"], precision=HI)
    q = rotary(q.reshape(n, T, d["H"], d["hd"]), theta, fraction)
    k = rotary(k.reshape(n, T, d["K"], d["hd"]), theta, fraction)
    v = v.reshape(n, T, d["K"], d["hd"])
    o = jax.lax.map(lambda qkv: _attend(*qkv), (q, k, v))
    h = h + jnp.einsum("nte,ed->ntd", o, a["wo"], precision=HI)
    x = rms_norm(h, w["ln2"], eps)
    m = w["mlp"]
    u = jnp.square(jax.nn.relu(jnp.einsum("ntd,df->ntf", x, m["wi"],
                                          precision=HI)))
    return h + jnp.einsum("ntf,fd->ntd", u, m["wo"], precision=HI)


def _f32_layer(w, bits: Optional[int]):
    out = jax.tree.map(lambda x: x.astype(jnp.float32), w)
    if bits is not None:
        for mod, name in _MATS:
            out[mod][name] = quantize(w[mod][name], bits)
    return out


@functools.lru_cache(maxsize=None)
def _jits(dkey: tuple, theta: float, fraction: float, eps: float):
    d = dict(dkey)

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(h, w, bits):
        return _layer(d, theta, fraction, eps, h, _f32_layer(w, bits))

    @jax.jit
    def embed(table, seqs):
        return table[seqs].astype(jnp.float32)

    @jax.jit
    def final(h, g):
        return rms_norm(h, g.astype(jnp.float32), eps)

    @functools.partial(jax.jit, static_argnums=(4,))
    def head(hr, hc, table, served, chunks):
        """Per position (rows of ``hr``): the reference's best logit and its
        logit for the served token; with a control (``hc``), the reference's
        logit for the token the control puts first."""
        R = hr.shape[0]
        V = table.shape[1]
        c = V // chunks

        def body(i, acc):
            best, at_served, cbest, at_cbest = acc
            w = jax.lax.dynamic_slice_in_dim(table, i * c, c, 1)
            w = w.astype(jnp.float32)
            lr = jnp.matmul(hr, w, precision=HI)
            best = jnp.maximum(best, lr.max(-1))
            j = served - i * c
            inside = (j >= 0) & (j < c)
            got = jnp.take_along_axis(lr, jnp.clip(j, 0, c - 1)[:, None],
                                      -1)[:, 0]
            at_served = jnp.where(inside, got, at_served)
            if hc is not None:
                lc = jnp.matmul(hc, w, precision=HI)
                top = lc.argmax(-1)
                topv = jnp.take_along_axis(lc, top[:, None], -1)[:, 0]
                take = topv > cbest
                cbest = jnp.where(take, topv, cbest)
                at_cbest = jnp.where(
                    take, jnp.take_along_axis(lr, top[:, None], -1)[:, 0],
                    at_cbest)
            return best, at_served, cbest, at_cbest

        ninf = jnp.full((R,), -jnp.inf, jnp.float32)
        return jax.lax.fori_loop(0, chunks, body, (ninf, ninf, ninf, ninf))

    return layer, embed, final, head


def head_chunks(V: int, target: int = 16384) -> int:
    n = -(-V // target)
    while V % n:
        n += 1
    return n


def logit_gaps(cfg: dict, seed: int, blocks,
               control_bits: Optional[int] = None):
    """Gap, per served token, between the reference's best logit and the
    logit it gives the served token.  ``blocks`` is a list of (seqs, served),
    both (n, T): each row a request's prompt and served tokens, and at each
    position the token served from that position's logits, or -1.

    The reference runs layer by layer over every block, so that one layer's
    weights are on the device at a time.  With ``control_bits``, also the gap
    of the token that the control (the reference at that precision) puts
    first at each of those positions.  Returns (gaps, control gaps or None)
    as numpy arrays, positions in block and row order."""
    d = dims(cfg)
    dtype = cfg["torch_dtype"]
    bits = 8 if cfg["weights"] == "int8" else None
    layer, embed, final, head = _jits(
        tuple(sorted(d.items())), float(cfg["rope_theta"]),
        float(cfg["rotary_fraction"]), float(cfg["norm_eps"]))
    ctl = control_bits is not None
    with jax.default_matmul_precision("highest"):
        table = W.table(cfg, seed, "embed", dtype)
        hs = [embed(table, jnp.asarray(s, jnp.int32)) for s, _ in blocks]
        del table
        hcs = list(hs) if ctl else [None] * len(hs)
        for l in range(d["L"]):
            w = W.layer_params(cfg, seed, l, dtype)
            hs = [layer(h, w, bits) for h in hs]
            if ctl:
                hcs = [layer(h, w, control_bits) for h in hcs]
            del w
        g = W.table(cfg, seed, "final_norm", dtype)
        table = W.table(cfg, seed, "lm_head", dtype)
        gaps, cgaps = [], []
        for (_, served), h, hc in zip(blocks, hs, hcs):
            keep = served.reshape(-1) >= 0
            flat = lambda x: final(x, g).reshape(-1, x.shape[-1])  # noqa: E731
            best, at_served, _, at_cbest = head(
                flat(h), flat(hc) if ctl else None, table,
                jnp.asarray(served.reshape(-1), jnp.int32),
                head_chunks(d["V"]))
            gaps.append(np.asarray(best - at_served)[keep])
            if ctl:
                cgaps.append(np.asarray(best - at_cbest)[keep])
    return (np.concatenate(gaps),
            np.concatenate(cgaps) if ctl else None)
