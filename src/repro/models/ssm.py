"""Attention-free sequence mixers: RWKV6 ("Finch") and Mamba2 (SSD).

Both are written around a single-step transition function that is reused by
(i) lax.scan for train/prefill and (ii) the serving decode step, so the
recurrent state layout is identical across phases.  The Pallas kernel
``repro.kernels.rwkv6_scan`` implements the chunked form of the RWKV6
recurrence for TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers

# =============================================================================
# RWKV6
# =============================================================================

_LORA_MIX = 32
_LORA_DECAY = 64


def init_rwkv6_layer(key, d_model: int, d_ff: int, head_dim: int, dtype):
    D, A, A2 = d_model, _LORA_MIX, _LORA_DECAY
    H = D // head_dim
    ks = jax.random.split(key, 12)
    n = lambda k, sh, s: (jax.random.normal(k, sh) * s).astype(dtype)
    s = D ** -0.5
    return {
        "ln1_w": jnp.ones((D,), dtype), "ln1_b": jnp.zeros((D,), dtype),
        "ln2_w": jnp.ones((D,), dtype), "ln2_b": jnp.zeros((D,), dtype),
        "tm": {
            "maa_x": jnp.zeros((D,), dtype),
            "maa_wkvrg": jnp.zeros((5, D), dtype),
            "maa_w1": n(ks[0], (D, 5 * A), s),
            "maa_w2": n(ks[1], (5, A, D), A ** -0.5),
            "decay_w0": jnp.full((D,), -6.0, dtype),
            "decay_w1": n(ks[2], (D, A2), s),
            "decay_w2": n(ks[3], (A2, D), A2 ** -0.5),
            "u": n(ks[4], (H, head_dim), 0.5),
            "wr": n(ks[5], (D, D), s), "wk": n(ks[6], (D, D), s),
            "wv": n(ks[7], (D, D), s), "wg": n(ks[8], (D, D), s),
            "wo": n(ks[9], (D, D), s),
            "lnx_w": jnp.ones((D,), dtype), "lnx_b": jnp.zeros((D,), dtype),
        },
        "cm": {
            "maa_k": jnp.zeros((D,), dtype), "maa_r": jnp.zeros((D,), dtype),
            "wk": n(ks[10], (D, d_ff), s),
            "wv": n(ks[11], (d_ff, D), d_ff ** -0.5),
            "wr": n(ks[0], (D, D), s),
        },
    }


def _rwkv6_projections(tm, x, sx):
    """x, sx: (B, T, D) -> (r, k, v, g, w) each (B, T, D) f32 (w = decay)."""
    xf = x.astype(jnp.float32)
    sxf = sx.astype(jnp.float32)
    xxx = xf + sxf * tm["maa_x"].astype(jnp.float32)
    lora = jnp.tanh(jnp.einsum("btd,da->bta", xxx, tm["maa_w1"].astype(jnp.float32)))
    B, T, _ = x.shape
    lora = lora.reshape(B, T, 5, _LORA_MIX)
    mix = jnp.einsum("btsa,sad->btsd", lora, tm["maa_w2"].astype(jnp.float32))
    mixes = tm["maa_wkvrg"].astype(jnp.float32)[None, None] + mix  # (B,T,5,D)
    xw, xk, xv, xr, xg = [xf + sxf * mixes[:, :, i] for i in range(5)]
    w = jnp.exp(
        -jnp.exp(
            tm["decay_w0"].astype(jnp.float32)
            + jnp.tanh(xw @ tm["decay_w1"].astype(jnp.float32))
            @ tm["decay_w2"].astype(jnp.float32)
        )
    )  # (B,T,D) in (0,1): data-dependent decay (the Finch contribution)
    r = xr @ tm["wr"].astype(jnp.float32)
    k = xk @ tm["wk"].astype(jnp.float32)
    v = xv @ tm["wv"].astype(jnp.float32)
    g = jax.nn.silu(xg @ tm["wg"].astype(jnp.float32))
    return r, k, v, g, w


def rwkv6_wkv_step(state, r, k, v, w, u):
    """One recurrence step.

    state: (B, H, hd, hd) [key-dim, value-dim]; r/k/v/w: (B, H, hd); u: (H, hd).
    """
    kv = k[..., :, None] * v[..., None, :]            # (B,H,hd,hd)
    out = jnp.einsum("bhi,bhij->bhj", r, u[None, :, :, None] * kv + state)
    state = w[..., :, None] * state + kv
    return state, out


def rwkv6_time_mix(tm, x, head_dim: int, state=None, shift_prev=None):
    """x: (B,T,D). Returns (y, (wkv_state, last_x))."""
    B, T, D = x.shape
    H = D // head_dim
    prev = shift_prev if shift_prev is not None else jnp.zeros((B, D), x.dtype)
    x_shift = jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)
    sx = x_shift - x
    r, k, v, g, w = _rwkv6_projections(tm, x, sx)
    rh, kh, vh, wh = [
        t.reshape(B, T, H, head_dim).swapaxes(0, 1) for t in (r, k, v, w)
    ]  # (T,B,H,hd)
    u = tm["u"].astype(jnp.float32)
    s0 = (
        state.astype(jnp.float32)
        if state is not None
        else jnp.zeros((B, H, head_dim, head_dim), jnp.float32)
    )

    def body(s, inp):
        rt, kt, vt, wt = inp
        s, out = rwkv6_wkv_step(s, rt, kt, vt, wt, u)
        return s, out

    s_final, outs = jax.lax.scan(body, s0, (rh, kh, vh, wh))
    y = outs.swapaxes(0, 1).reshape(B, T, D)  # (B,T,D) f32
    y = layers.group_norm_heads(y, tm["lnx_w"], tm["lnx_b"], H)
    y = (y.astype(jnp.float32) * g) @ tm["wo"].astype(jnp.float32)
    return y.astype(x.dtype), (s_final, x[:, -1])


def rwkv6_channel_mix(cm, x, shift_prev=None):
    B, T, D = x.shape
    prev = shift_prev if shift_prev is not None else jnp.zeros((B, D), x.dtype)
    x_shift = jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)
    sx = x_shift - x
    xk = x + sx * cm["maa_k"]
    xr = x + sx * cm["maa_r"]
    k = jnp.square(jax.nn.relu(xk @ cm["wk"]))
    y = jax.nn.sigmoid(xr @ cm["wr"]) * (k @ cm["wv"])
    return y.astype(x.dtype), x[:, -1]


def rwkv6_block(p, x, head_dim: int, cache=None):
    """Full RWKV6 layer (time-mix + channel-mix). cache: dict or None."""
    c = cache or {}
    h, (wkv_state, tm_shift) = rwkv6_time_mix(
        p["tm"],
        layers.layer_norm(x, p["ln1_w"], p["ln1_b"]),
        head_dim,
        state=c.get("wkv"),
        shift_prev=c.get("tm_shift"),
    )
    x = x + h
    h, cm_shift = rwkv6_channel_mix(
        p["cm"],
        layers.layer_norm(x, p["ln2_w"], p["ln2_b"]),
        shift_prev=c.get("cm_shift"),
    )
    x = x + h
    new_cache = {"wkv": wkv_state, "tm_shift": tm_shift, "cm_shift": cm_shift}
    return x, new_cache


# =============================================================================
# Mamba2 (SSD, scalar-identity A per head, one group of B/C): zamba2's
# layers and granite's
# =============================================================================


def init_mamba2_mixer(key, d_model: int, d_inner: int, ssm_state: int,
                      head_dim: int, dtype, vec_dtype=jnp.float32):
    """The mixer's weights; ``vec_dtype`` stores the per-head A_log, D and
    dt_bias, which the mixer computes with in f32 whatever they are stored
    in."""
    nh = d_inner // head_dim
    S = ssm_state
    ks = jax.random.split(key, 3)
    proj_out = 2 * d_inner + 2 * S + nh
    return {
        "in_proj": (
            jax.random.normal(ks[0], (d_model, proj_out)) * d_model ** -0.5
        ).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (4, d_inner + 2 * S)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((d_inner + 2 * S,), dtype),
        "A_log": jnp.zeros((nh,), vec_dtype),
        "D_skip": jnp.ones((nh,), vec_dtype),
        "dt_bias": jnp.zeros((nh,), vec_dtype),
        "gnorm_w": jnp.ones((d_inner,), dtype),
        "out_proj": (
            jax.random.normal(ks[2], (d_inner, d_model)) * d_inner ** -0.5
        ).astype(dtype),
    }


def init_mamba2_layer(key, d_model: int, d_inner: int, ssm_state: int,
                      head_dim: int, dtype):
    """The mixer with its own pre-norm (zamba2's Mamba layers)."""
    return {"norm_w": jnp.ones((d_model,), dtype),
            **init_mamba2_mixer(key, d_model, d_inner, ssm_state, head_dim,
                                dtype)}


def _causal_depthwise_conv(x, w, b, conv_state=None):
    """x: (B,T,C), w: (W,C). Returns (y (B,T,C), new_state (B,W-1,C))."""
    W = w.shape[0]
    B, T, C = x.shape
    prev = (
        conv_state
        if conv_state is not None
        else jnp.zeros((B, W - 1, C), x.dtype)
    )
    xp = jnp.concatenate([prev, x], axis=1)  # (B, T+W-1, C)
    y = sum(xp[:, i : i + T] * w[i] for i in range(W)) + b
    return jax.nn.silu(y), xp[:, -(W - 1):]


_HI = jax.lax.Precision.HIGHEST


def ssd_step(h, x_t, B_t, C_t, dt_t, A):
    """One step of the recurrence: h (B,nh,hd,S) f32; x_t (B,nh,hd); B_t,
    C_t (B,S); dt_t (B,nh).  Returns (h', y_t (B,nh,hd))."""
    decay = jnp.exp(A * dt_t)
    h = decay[..., None, None] * h + (dt_t[..., None] * x_t)[..., None] \
        * B_t[:, None, None, :]
    return h, jnp.einsum("bnds,bs->bnd", h, C_t, precision=_HI)


def ssd_scan(x, dt, A, Bs, Cs, h0):
    """The recurrence token by token: x (B,T,nh,hd), dt (B,T,nh), Bs/Cs
    (B,T,S), h0 (B,nh,hd,S), all f32.  Returns (y (B,T,nh,hd), h_T)."""
    def body(h, inp):
        return ssd_step(h, *inp, A)

    seq = tuple(a.swapaxes(0, 1) for a in (x, Bs, Cs, dt))
    h, ys = jax.lax.scan(body, h0, seq)
    return ys.swapaxes(0, 1), h


def ssd_chunked(x, dt, A, Bs, Cs, h0, chunk: int):
    """The same recurrence in chunks (Mamba2's SSD form): within a chunk,
    matrix products over its positions; between chunks, the state carried.
    Shapes as :func:`ssd_scan`.  A ragged tail is padded with dt = 0, which
    leaves the state unchanged."""
    B, T, nh, hd = x.shape
    l = min(chunk, T)
    pad = (-T) % l
    if pad:
        x, dt, Bs, Cs = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                         for a in (x, dt, Bs, Cs))
    c = (T + pad) // l
    x = x.reshape(B, c, l, nh, hd)
    dt = dt.reshape(B, c, l, nh)
    Bs = Bs.reshape(B, c, l, -1)
    Cs = Cs.reshape(B, c, l, -1)
    a = jnp.cumsum(A * dt, axis=2)                      # (B,c,l,nh), <= 0
    a_h = jnp.moveaxis(a, -1, 2)                        # (B,c,nh,l)
    # decay from position j to i inside a chunk, i >= j
    seg = a_h[..., :, None] - a_h[..., None, :]         # (B,c,nh,l,l)
    causal = jnp.tril(jnp.ones((l, l), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    xdt = x * dt[..., None]                             # (B,c,l,nh,hd)
    cb = jnp.einsum("bcis,bcjs->bcij", Cs, Bs, precision=_HI)
    y = jnp.einsum("bcij,bchij,bcjhd->bcihd", cb, decay, xdt, precision=_HI)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(a[:, :, -1:] - a)                  # (B,c,l,nh)
    states = jnp.einsum("bcjs,bcjh,bcjhd->bchds", Bs, to_end, xdt,
                        precision=_HI)                  # (B,c,nh,hd,S)
    whole = jnp.exp(a[:, :, -1])                        # (B,c,nh)

    def carry(h, inp):
        st, w = inp
        return w[..., None, None] * h + st, h

    h, h_in = jax.lax.scan(carry, h0, (states.swapaxes(0, 1),
                                       whole.swapaxes(0, 1)))
    h_in = h_in.swapaxes(0, 1)                          # state entering each
    y = y + jnp.einsum("bcis,bchds,bcih->bcihd", Cs, h_in, jnp.exp(a),
                       precision=_HI)
    return y.reshape(B, c * l, nh, hd)[:, :T], h


def mamba2_mix(p, x, *, head_dim: int, ssm_state: int, cache=None,
               chunk: int = 256):
    """x: (B,T,D). Returns (y, new_cache).  A single token (decode) takes
    one step of the recurrence; a sequence runs in chunks of ``chunk``."""
    B, T, D = x.shape
    c = cache or {}
    zxbcdt = x @ p["in_proj"]
    d_inner = p["out_proj"].shape[0]
    nh = d_inner // head_dim
    S = ssm_state
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * S], axis=-1)
    xBC, conv_state = _causal_depthwise_conv(
        xBC, p["conv_w"], p["conv_b"], c.get("conv")
    )
    xs, Bs, Cs = jnp.split(xBC, [d_inner, d_inner + S], axis=-1)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f32(dt) + f32(p["dt_bias"]))                  # (B,T,nh)
    A = -jnp.exp(f32(p["A_log"]))                                      # (nh,)
    xh = f32(xs).reshape(B, T, nh, head_dim)
    Bs, Cs = f32(Bs), f32(Cs)
    h0 = (
        f32(c["ssm"])
        if "ssm" in c
        else jnp.zeros((B, nh, head_dim, S), jnp.float32)
    )
    if T == 1:
        h_final, y = ssd_step(h0, xh[:, 0], Bs[:, 0], Cs[:, 0], dt[:, 0], A)
        y = y[:, None]
    else:
        y, h_final = ssd_chunked(xh, dt, A, Bs, Cs, h0, chunk)
    y = y + f32(p["D_skip"])[:, None] * xh                             # (B,T,nh,hd)
    y = y.reshape(B, T, d_inner)
    y = layers.rms_norm(y * jax.nn.silu(f32(z)), p["gnorm_w"])
    y = y.astype(x.dtype) @ p["out_proj"]
    return y, {"conv": conv_state, "ssm": h_final}


def mamba2_block(p, x, *, head_dim: int, ssm_state: int, cache=None):
    h, new_cache = mamba2_mix(
        p,
        layers.rms_norm(x, p["norm_w"]),
        head_dim=head_dim,
        ssm_state=ssm_state,
        cache=cache,
    )
    return x + h, new_cache
