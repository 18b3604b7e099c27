"""The plain reference: the configuration's forward pass in ``jax.numpy``.

Float32 throughout, every matrix product at ``Precision.HIGHEST``, no cache,
no kernels, no batching tricks; weights regenerated from the seed one layer
at a time (``harness.weights``), so it takes nothing the program made.  The
equations are those of the configuration's architecture module
(``harness.arch``): its embedding, the layer function it chooses for each
layer index, its final norm and its output table.  This file drives them,
layer by layer over every block of requests.

An ``int8`` configuration serves the matrices the module names (``MATS``) as
per-output-channel symmetric int8 with an f32 scale; the reference quantizes
the same bf16 weights itself, by that rule, and computes on the dequantized
values.  ``bits`` computes a control in a lower precision: those matrices
quantized to ``bits`` per weight by the same rule.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import arch, weights as W


def quantize(w, bits: int):
    """Per-output-channel symmetric quantization, dequantized to f32."""
    w = w.astype(jnp.float32)
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2), 1e-8) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def _f32_layer(mats, w, bits: Optional[int]):
    out = jax.tree.map(lambda x: x.astype(jnp.float32), w)
    if bits is not None:
        for *outer, name in mats:     # a layer may hold some of them only
            src, dst = w, out
            for p in outer:
                src, dst = src.get(p, {}), dst.get(p, {})
            if name in src:
                dst[name] = quantize(src[name], bits)
    return out


@functools.lru_cache(maxsize=None)
def _jits(A, dkey: tuple):
    d = dict(dkey)
    axis = A.HEAD[1]

    def one(f):
        return functools.partial(jax.jit, static_argnums=(2,))(
            lambda h, w, bits: f(d, h, _f32_layer(A.MATS, w, bits)))

    layers = {kind: one(f) for kind, f in A.LAYERS.items()}

    @jax.jit
    def embed(table, seqs):
        return A.embed(d, table, seqs)

    @jax.jit
    def final(h, g):
        return A.final(d, h, g)

    @functools.partial(jax.jit, static_argnums=(4,))
    def head(hr, hc, table, served, chunks):
        """Per position (rows of ``hr``): the reference's best logit and its
        logit for the served token; with a control (``hc``), the reference's
        logit for the token the control puts first."""
        R = hr.shape[0]
        V = table.shape[axis]
        c = V // chunks

        def body(i, acc):
            best, at_served, cbest, at_cbest = acc
            w = jax.lax.dynamic_slice_in_dim(table, i * c, c, axis)
            w = w.astype(jnp.float32)
            lr = A.logits(d, hr, w)
            best = jnp.maximum(best, lr.max(-1))
            j = served - i * c
            inside = (j >= 0) & (j < c)
            got = jnp.take_along_axis(lr, jnp.clip(j, 0, c - 1)[:, None],
                                      -1)[:, 0]
            at_served = jnp.where(inside, got, at_served)
            if hc is not None:
                lc = A.logits(d, hc, w)
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

    return layers, embed, final, head


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
    A = arch.of(cfg)
    d = A.dims(cfg)
    dtype = cfg["torch_dtype"]
    bits = 8 if cfg["weights"] == "int8" else None
    layers, embed, final, head = _jits(A, tuple(sorted(d.items())))
    ctl = control_bits is not None
    out_name, axis = A.HEAD
    with jax.default_matmul_precision("highest"):
        table = W.table(cfg, seed, A.EMBED, dtype)
        hs = [embed(table, jnp.asarray(s, jnp.int32)) for s, _ in blocks]
        del table
        hcs = list(hs) if ctl else [None] * len(hs)
        for l in range(A.num_layers(d)):
            layer = layers[A.layer_at(d, l)[0]]
            w = W.layer_params(cfg, seed, l, dtype)
            hs = [layer(h, w, bits) for h in hs]
            if ctl:
                hcs = [layer(h, w, control_bits) for h in hcs]
            del w
        g = W.table(cfg, seed, A.FINAL_NORM, dtype)
        table = W.table(cfg, seed, out_name, dtype)
        gaps, cgaps = [], []
        for (_, served), h, hc in zip(blocks, hs, hcs):
            keep = served.reshape(-1) >= 0
            flat = lambda x: final(x, g).reshape(-1, x.shape[-1])  # noqa: E731
            best, at_served, _, at_cbest = head(
                flat(h), flat(hc) if ctl else None, table,
                jnp.asarray(served.reshape(-1), jnp.int32),
                head_chunks(table.shape[axis]))
            gaps.append(np.asarray(best - at_served)[keep])
            if ctl:
                cgaps.append(np.asarray(best - at_cbest)[keep])
    return (np.concatenate(gaps),
            np.concatenate(cgaps) if ctl else None)
