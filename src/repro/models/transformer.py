"""Model assembly for all assigned families.

Families:
  dense / moe / vlm : decoder-only transformer, scan-over-layers (stacked
                      params, O(1) HLO in depth — required for the 80-layer
                      qwen1.5-110b to compile quickly).
  ssm (rwkv6)       : scan-over-layers of RWKV6 blocks.
  hybrid (zamba2)   : nested scan — groups of ``attn_every`` Mamba2 layers,
                      each group followed by a SHARED (weight-tied) attention
                      block with a per-group norm gain.
  audio (whisper)   : enc-dec; conv/mel frontend stubbed (embeddings in).

Three entry points, used by training, serving and the dry-run:
  forward(params, cfg, batch)                -> logits (B, S, V) f32
  prefill(params, cfg, batch, max_seq)       -> (logits_last, cache)
  decode_step(params, cfg, cache, tokens)    -> (logits, cache)
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import ctx
from repro.models import layers, moe, rope, ssm
from repro.models.attention import attention, decode_attention

# =============================================================================
# init
# =============================================================================


def _init_attn(key, cfg: ModelConfig, dtype):
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = D ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (D, H * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (D, K * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (D, K * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (H * hd, D)) * (H * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _init_decoder_layer(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": _init_attn(k1, cfg, dtype),
    }
    if cfg.is_moe:
        p["moe_block"] = moe.init_moe_block(k2, cfg, dtype)
    else:
        p["mlp"] = layers.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return p


def _init_xattn_layer(key, cfg: ModelConfig, dtype):
    """Whisper decoder layer: self-attn + cross-attn + gelu mlp."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "lnx": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": _init_attn(k1, cfg, dtype),
        "xattn": _init_attn(k2, cfg, dtype),
        "mlp": layers.init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.mlp, dtype),
    }


def _stacked(init_fn, key, n, *args):
    return jax.vmap(lambda k: init_fn(k, *args))(jax.random.split(key, n))


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = cfg.jnp_dtype
    ke, kl, kh, ko = jax.random.split(key, 4)
    D, V = cfg.d_model, cfg.vocab_size
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(ke, (V, D)) * 0.02).astype(dtype),
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(ko, (D, V)) * D ** -0.5).astype(dtype)

    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = _stacked(_init_decoder_layer, kl, cfg.num_layers, cfg, dtype)
    elif cfg.family == "ssm":
        params["layers"] = _stacked(
            ssm.init_rwkv6_layer, kl, cfg.num_layers,
            cfg.d_model, cfg.d_ff, cfg.ssm_head_dim, dtype,
        )
    elif cfg.family == "hybrid":
        G = cfg.num_layers // cfg.attn_every
        params["mamba_layers"] = _stacked(
            ssm.init_mamba2_layer, kl, cfg.num_layers,
            cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim, dtype,
        )
        params["shared"] = _init_decoder_layer(kh, cfg, dtype)
        params["group_gain"] = jnp.ones((G, D), dtype)
    elif cfg.family == "audio":
        params["enc_layers"] = _stacked(
            _init_decoder_layer, kl, cfg.encoder_layers, cfg, dtype
        )
        params["enc_final_norm"] = jnp.ones((D,), dtype)
        params["dec_layers"] = _stacked(_init_xattn_layer, kh, cfg.num_layers, cfg, dtype)
    else:
        raise ValueError(cfg.family)
    return params


def random_checkpoint(cfg: ModelConfig, seed: int = 0) -> Dict[str, Any]:
    """Random weights from ``seed``, made on the device under jit and brought
    to the host: a checkpoint to deploy from that leaves no copy of the
    weights on the device (the deploy uploads the one it serves)."""
    init = jax.jit(init_params, static_argnums=0)
    return jax.device_get(init(cfg, jax.random.PRNGKey(seed)))


# =============================================================================
# layer-stack iteration: scan (O(1) HLO) or python unroll (accurate HLO costs)
# =============================================================================


def _scan_layers(body, x, xs, unroll: bool = False):
    if not unroll:
        return jax.lax.scan(body, x, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x, y = body(x, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = ys[0] if ys else None
    return x, ys


# =============================================================================
# attention sublayer (shared by full-seq and decode paths)
# =============================================================================


def _qkv(p, cfg: ModelConfig, x, angles):
    B = x.shape[0]
    S = x.shape[1]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = layers.dense(x, p["wq"], p.get("bq")).reshape(B, S, H, hd)
    k = layers.dense(x, p["wk"], p.get("bk")).reshape(B, S, K, hd)
    v = layers.dense(x, p["wv"], p.get("bv")).reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if angles is not None:
        q = rope.apply_rotary(q, angles)
        k = rope.apply_rotary(k, angles)
    return q, k, v


def _self_attention_full(p, cfg, x, angles, *, causal=True, window=None):
    """Full-sequence self attention. Returns (out, (k, v))."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, angles)
    o = attention(q, k, v, causal=causal, window=window)
    return layers.dense(o.reshape(B, S, -1), p["wo"]), (k, v)


def _self_attention_decode(p, cfg, x, angles, kc, vc, lengths, *, window=None):
    """One-token self attention against a read-only cache.

    x: (B, 1, D); kc/vc: (B, Smax, K, hd) holding the tokens BEFORE this one;
    lengths: (B,) their count.  Returns (out (B,1,D), k_new, v_new): this
    token's k/v, (B, K, hd) in the cache dtype.  Attention joins them to the
    cache's entries in one softmax; the cache itself is not written here —
    ``decode_step`` writes every layer's k_new/v_new at ``lengths`` once,
    after the layer loop (``_append_kv``).

    When a sliding window is active and much smaller than the cache, only the
    last ``window`` cache entries are gathered and attended — decode compute
    is O(window), not O(cache) (the long_500k sub-quadratic path).
    """
    B = x.shape[0]
    S = kc.shape[1]
    q, k, v = _qkv(p, cfg, x, angles)  # k,v: (B,1,K,hd)
    k_new, v_new = k[:, 0].astype(kc.dtype), v[:, 0].astype(vc.dtype)
    if window is not None and S > 2 * window:
        start = jnp.maximum(lengths + 1 - window, 0)              # (B,)
        idx = start[:, None] + jnp.arange(window, dtype=jnp.int32)[None, :]
        idx = jnp.minimum(idx, S - 1)
        kw = jnp.take_along_axis(kc, idx[:, :, None, None], axis=1)
        vw = jnp.take_along_axis(vc, idx[:, :, None, None], axis=1)
        o = decode_attention(q[:, 0], kw, vw, lengths - start,
                             k_new=k_new, v_new=v_new)
    else:
        o = decode_attention(q[:, 0], kc, vc, lengths, window=window,
                             k_new=k_new, v_new=v_new)
    return layers.dense(o.reshape(B, 1, -1), p["wo"]), k_new, v_new


def _append_kv(cache, k_new, v_new, lengths, uniform: bool):
    """Write every layer's new k/v, (L, B, K, hd), into the (L, B, S, K, hd)
    slabs at each slot's ``lengths``: one in-place update of the donated
    cache after the layer loop.  Returns the cache with lengths += 1.

    uniform=True (every slot at one position) writes with a scalar
    dynamic-update-slice, which GSPMD partitions on a sharded sequence dim
    without the f32 set->add scatter rewrite (2x write traffic).
    """
    if uniform:
        at = (0, 0, lengths[0], 0, 0)
        k = jax.lax.dynamic_update_slice(cache["k"], k_new[:, :, None], at)
        v = jax.lax.dynamic_update_slice(cache["v"], v_new[:, :, None], at)
    else:
        bidx = jnp.arange(lengths.shape[0])
        k = cache["k"].at[:, bidx, lengths].set(
            k_new, unique_indices=True, mode="promise_in_bounds")
        v = cache["v"].at[:, bidx, lengths].set(
            v_new, unique_indices=True, mode="promise_in_bounds")
    return dict(cache, k=k, v=v, lengths=lengths + 1)


def _cross_attention(p, cfg, x, enc_k, enc_v):
    B, S, _ = x.shape
    q, _, _ = _qkv(p, cfg, x, None)
    o = attention(q, enc_k, enc_v, causal=False)
    return layers.dense(o.reshape(B, S, -1), p["wo"])


def _enc_kv(p, cfg, enc_out):
    B, T, _ = enc_out.shape
    K, hd = cfg.num_kv_heads, cfg.head_dim
    k = layers.dense(enc_out, p["wk"], p.get("bk")).reshape(B, T, K, hd)
    v = layers.dense(enc_out, p["wv"], p.get("bv")).reshape(B, T, K, hd)
    return k, v


def _ffn(p, cfg: ModelConfig, x):
    """Returns (out, aux_loss)."""
    if cfg.is_moe:
        return moe.apply_moe_block(p["moe_block"], x, cfg)
    return layers.apply_mlp(p["mlp"], x, cfg.mlp), jnp.float32(0.0)


def _decoder_layer(p, cfg, x, angles, *, window, collect_kv, remat=False):
    """Standard pre-norm decoder layer. Returns (x, kv_or_None, aux)."""

    def body(p, x, angles):
        x = ctx.constrain(x, ("dp", None, None))
        h, kv = _self_attention_full(
            p["attn"], cfg, layers.rms_norm(x, p["ln1"], cfg.norm_eps),
            angles, window=window,
        )
        x = x + h
        h, aux = _ffn(p, cfg, layers.rms_norm(x, p["ln2"], cfg.norm_eps))
        return x + h, kv, aux

    if remat:
        body = jax.checkpoint(body)
    x, kv, aux = body(p, x, angles)
    return x, (kv if collect_kv else None), aux


# =============================================================================
# full-sequence forward (training / prefill scoring)
# =============================================================================


def _rope_angles_for(cfg: ModelConfig, batch, B, S):
    if cfg.rope_theta == 0.0:  # whisper: sinusoidal abs positions, no rope
        return None
    if cfg.mrope:
        pos = batch.get("positions")
        if pos is None:
            p = rope.positions_default(B, S)
            pos = jnp.stack([p, p, p])  # text-only: t==h==w
        return rope.mrope_angles(pos, cfg.head_dim, cfg.rope_theta,
                                 cfg.mrope_sections)
    pos = batch.get("positions")
    if pos is None:
        pos = rope.positions_default(B, S)
    return rope.rope_angles(pos, cfg.head_dim, cfg.rope_theta)


def _sinusoid(S, D):
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    i = jnp.arange(D // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * i / D))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed_in(params, cfg, batch):
    if batch.get("embeds") is not None:
        x = batch["embeds"].astype(cfg.jnp_dtype)
    else:
        x = layers.embed(batch["tokens"], params["embed"])
    # pin batch sharding on the residual stream entry (the embedding table's
    # own sharding must not leak onto activations)
    return ctx.constrain(x, ("dp", None, None))


def _lm_logits(params, cfg, x, logits_for: str = "all"):
    if logits_for == "last":
        x = x[:, -1:]
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return layers.unembed(x, table)


def forward(params, cfg: ModelConfig, batch, *, remat: bool = False,
            collect_kv: bool = False, logits_for: str = "all"):
    """Full-sequence scoring. Returns dict(logits, aux_loss [, kv]).

    logits_for="last" computes the LM head on the final position only (the
    prefill path: avoids materializing the (B, S, V) logits tensor).
    """
    if cfg.family == "audio":
        return _forward_whisper(params, cfg, batch, collect_kv=collect_kv,
                                logits_for=logits_for)

    x = _embed_in(params, cfg, batch)
    B, S, _ = x.shape
    aux_total = jnp.float32(0.0)

    if cfg.family in ("dense", "moe", "vlm"):
        angles = _rope_angles_for(cfg, batch, B, S)
        window = cfg.attn_window

        def body(x, lp):
            y, kv, aux = _decoder_layer(
                lp, cfg, x, angles, window=window,
                collect_kv=collect_kv, remat=remat,
            )
            return y, (kv, aux)

        x, (kvs, auxs) = _scan_layers(body, x, params["layers"], unroll=cfg.unroll_layers)
        aux_total = auxs.sum()
        out = {"logits": _lm_logits(params, cfg, x, logits_for),
               "aux_loss": aux_total}
        if collect_kv:
            out["kv"] = kvs  # (k,v) each (L,B,S,K,hd)
        return out

    if cfg.family == "ssm":
        def body(x, lp):
            x = ctx.constrain(x, ("dp", None, None))
            y, cache = ssm.rwkv6_block(lp, x, cfg.ssm_head_dim)
            return y, cache if collect_kv else None

        x, caches = _scan_layers(body, x, params["layers"], unroll=cfg.unroll_layers)
        out = {"logits": _lm_logits(params, cfg, x, logits_for),
               "aux_loss": aux_total}
        if collect_kv:
            out["state"] = caches
        return out

    if cfg.family == "hybrid":
        return _forward_hybrid(params, cfg, batch, x, collect_kv=collect_kv,
                               remat=remat, logits_for=logits_for)

    raise ValueError(cfg.family)


def _forward_hybrid(params, cfg, batch, x, *, collect_kv, remat=False,
                    logits_for: str = "all"):
    B, S, _ = x.shape
    G = cfg.num_layers // cfg.attn_every
    angles = _rope_angles_for(cfg, batch, B, S)
    mamba_stacked = jax.tree.map(
        lambda a: a.reshape(G, cfg.attn_every, *a.shape[1:]),
        params["mamba_layers"],
    )
    shared = params["shared"]

    def group_body(x, inp):
        mp, gain = inp

        def mamba_body(x, lp):
            x = ctx.constrain(x, ("dp", None, None))
            y, cache = ssm.mamba2_block(
                lp, x, head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state
            )
            return y, cache if collect_kv else None

        x, mcaches = _scan_layers(mamba_body, x, mp, unroll=cfg.unroll_layers)
        # shared (weight-tied) attention block, per-group input gain
        xg = x * gain
        y, kv, _ = _decoder_layer(
            shared, cfg, xg, angles, window=cfg.attn_window,
            collect_kv=collect_kv, remat=remat,
        )
        return y, (mcaches, kv)

    x, (mcaches, kvs) = _scan_layers(
        group_body, x, (mamba_stacked, params["group_gain"]),
        unroll=cfg.unroll_layers,
    )
    out = {"logits": _lm_logits(params, cfg, x, logits_for),
           "aux_loss": jnp.float32(0.0)}
    if collect_kv:
        out["state"] = mcaches  # leaves: (G, ae, B, ...)
        out["kv"] = kvs         # (G, B, S, K, hd) pair
    return out


def _forward_whisper(params, cfg, batch, *, collect_kv=False,
                     logits_for: str = "all"):
    """batch: frames (B, enc_seq, D) from the stub frontend + decoder tokens."""
    frames = batch["frames"]
    B = frames.shape[0]
    enc = frames.astype(cfg.jnp_dtype) + _sinusoid(
        frames.shape[1], cfg.d_model
    ).astype(cfg.jnp_dtype)

    def enc_body(x, lp):
        x = ctx.constrain(x, ("dp", None, None))
        y, _, _ = _decoder_layer(lp, cfg, x, None, window=None,
                                 collect_kv=False)
        return y, None

    enc, _ = _scan_layers(enc_body, enc, params["enc_layers"], unroll=cfg.unroll_layers)
    enc = layers.rms_norm(enc, params["enc_final_norm"], cfg.norm_eps)

    tokens = batch["tokens"]
    S = tokens.shape[1]
    x = layers.embed(tokens, params["embed"]) + _sinusoid(
        S, cfg.d_model
    ).astype(cfg.jnp_dtype)

    def dec_body(x, lp):
        x = ctx.constrain(x, ("dp", None, None))
        h, kv = _self_attention_full(
            lp["attn"], cfg, layers.rms_norm(x, lp["ln1"], cfg.norm_eps), None
        )
        x = x + h
        ek, ev = _enc_kv(lp["xattn"], cfg, enc)
        x = x + _cross_attention(
            lp["xattn"], cfg, layers.rms_norm(x, lp["lnx"], cfg.norm_eps), ek, ev
        )
        h, _ = _ffn(lp, cfg, layers.rms_norm(x, lp["ln2"], cfg.norm_eps))
        x = x + h
        return x, (kv, (ek, ev)) if collect_kv else None

    x, kvs = _scan_layers(dec_body, x, params["dec_layers"], unroll=cfg.unroll_layers)
    out = {"logits": _lm_logits(params, cfg, x, logits_for),
           "aux_loss": jnp.float32(0.0)}
    if collect_kv:
        out["kv"] = kvs
    return out


# =============================================================================
# serving: cache init / prefill / decode_step
# =============================================================================


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int, dtype=None):
    """Allocate the decode cache for ``batch_size`` slots of ``max_seq``."""
    dt = dtype or cfg.jnp_dtype
    B, L = batch_size, cfg.num_layers
    K, hd = cfg.num_kv_heads, cfg.head_dim
    lengths = jnp.zeros((B,), jnp.int32)
    if cfg.family in ("dense", "moe", "vlm"):
        return {
            "k": jnp.zeros((L, B, max_seq, K, hd), dt),
            "v": jnp.zeros((L, B, max_seq, K, hd), dt),
            "lengths": lengths,
        }
    if cfg.family == "ssm":
        H = cfg.d_model // cfg.ssm_head_dim
        return {
            "wkv": jnp.zeros((L, B, H, cfg.ssm_head_dim, cfg.ssm_head_dim),
                             jnp.float32),
            "tm_shift": jnp.zeros((L, B, cfg.d_model), dt),
            "cm_shift": jnp.zeros((L, B, cfg.d_model), dt),
            "lengths": lengths,
        }
    if cfg.family == "hybrid":
        G = L // cfg.attn_every
        nh = cfg.d_inner // cfg.ssm_head_dim
        return {
            "conv": jnp.zeros((L, B, 3, cfg.d_inner + 2 * cfg.ssm_state), dt),
            "ssm": jnp.zeros((L, B, nh, cfg.ssm_head_dim, cfg.ssm_state),
                             jnp.float32),
            "k": jnp.zeros((G, B, max_seq, K, hd), dt),
            "v": jnp.zeros((G, B, max_seq, K, hd), dt),
            "lengths": lengths,
        }
    if cfg.family == "audio":
        return {
            "k": jnp.zeros((L, B, max_seq, K, hd), dt),
            "v": jnp.zeros((L, B, max_seq, K, hd), dt),
            "xk": jnp.zeros((L, B, cfg.encoder_seq, K, hd), dt),
            "xv": jnp.zeros((L, B, cfg.encoder_seq, K, hd), dt),
            "lengths": lengths,
        }
    raise ValueError(cfg.family)


def prefill(params, cfg: ModelConfig, batch, max_seq: int):
    """Run the prompt through the model, build the decode cache.

    batch["tokens"]: (B, S) with S <= max_seq (uniform prompt length; ragged
    admission is handled by the serving scheduler upstream).
    Returns (last_logits (B, V), cache).
    """
    out = forward(params, cfg, batch, collect_kv=True, logits_for="last")
    B = batch["tokens"].shape[0] if batch.get("tokens") is not None else batch[
        "embeds"
    ].shape[0]
    S = (
        batch["tokens"].shape[1]
        if batch.get("tokens") is not None
        else batch["embeds"].shape[1]
    )
    cache = init_cache(cfg, B, max_seq)
    lengths = jnp.full((B,), S, jnp.int32)
    if cfg.family in ("dense", "moe", "vlm"):
        k, v = out["kv"]
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0)
        )
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0)
        )
    elif cfg.family == "ssm":
        st = out["state"]
        cache["wkv"] = st["wkv"]
        cache["tm_shift"] = st["tm_shift"].astype(cache["tm_shift"].dtype)
        cache["cm_shift"] = st["cm_shift"].astype(cache["cm_shift"].dtype)
    elif cfg.family == "hybrid":
        st = out["state"]
        L = cfg.num_layers
        cache["conv"] = st["conv"].reshape(L, *st["conv"].shape[2:]).astype(
            cache["conv"].dtype
        )
        cache["ssm"] = st["ssm"].reshape(L, *st["ssm"].shape[2:])
        k, v = out["kv"]
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0)
        )
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0)
        )
    elif cfg.family == "audio":
        kv, xkv = out["kv"]
        k, v = kv
        ek, ev = xkv
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0)
        )
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0)
        )
        cache["xk"], cache["xv"] = (
            ek.astype(cache["xk"].dtype),
            ev.astype(cache["xv"].dtype),
        )
    cache["lengths"] = lengths
    logits = out["logits"][:, -1]
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache, tokens, positions=None,
                uniform_lengths: bool = False):
    """One decode step for every active slot.

    tokens: (B,) int32 (the previously sampled token). Returns
    (logits (B, V) f32, updated cache with lengths += 1).

    The layer loop only reads the K/V slabs and emits each layer's new k/v;
    ``_append_kv`` writes them into the (donated) slabs once, after the loop,
    so no slab passes through the loop's outputs.

    uniform_lengths=True promises every slot is at the same position
    (lockstep decode pools / the dry-run serve_step): the write becomes a
    scalar dynamic-update-slice, which partitions cleanly.
    """
    lengths = cache["lengths"]
    B = tokens.shape[0]
    x = layers.embed(tokens, params["embed"])[:, None]  # (B,1,D)
    # native sliding window always applies; the long-context window variant
    # only engages for caches past 64k (dense archs stay full-attention at 32k)
    window = cfg.attn_window
    if window is None and cfg.long_context_window is not None:
        cache_S = cache["k"].shape[2] if "k" in cache else 0
        if cache_S > 65536:
            window = cfg.long_context_window

    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.mrope:
            if positions is None:
                p1 = lengths[None, :, None]
                positions = jnp.broadcast_to(p1, (3, B, 1))
            angles = rope.mrope_angles(
                positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
            )
        else:
            angles = rope.rope_angles(
                lengths[:, None], cfg.head_dim, cfg.rope_theta
            )

        def body(x, inp):
            lp, kc, vc = inp
            h, kn, vn = _self_attention_decode(
                lp["attn"], cfg,
                layers.rms_norm(x, lp["ln1"], cfg.norm_eps),
                angles, kc, vc, lengths, window=window,
            )
            x = x + h
            h, _ = _ffn(lp, cfg, layers.rms_norm(x, lp["ln2"], cfg.norm_eps))
            return x + h, (kn, vn)

        x, (kn, vn) = _scan_layers(
            body, x, (params["layers"], cache["k"], cache["v"]),
            unroll=cfg.unroll_layers,
        )
        cache = _append_kv(cache, kn, vn, lengths, uniform_lengths)
        return _lm_logits(params, cfg, x)[:, 0], cache

    if cfg.family == "ssm":
        def body(x, inp):
            lp, wkv, tms, cms = inp
            y, nc = ssm.rwkv6_block(
                lp, x, cfg.ssm_head_dim,
                cache={"wkv": wkv, "tm_shift": tms, "cm_shift": cms},
            )
            return y, (nc["wkv"], nc["tm_shift"], nc["cm_shift"])

        x, (wkv, tms, cms) = _scan_layers(
            body, x, (params["layers"], cache["wkv"], cache["tm_shift"],
                      cache["cm_shift"]),
            unroll=cfg.unroll_layers,
        )
        cache = dict(cache, wkv=wkv, tm_shift=tms.astype(cache["tm_shift"].dtype),
                     cm_shift=cms.astype(cache["cm_shift"].dtype),
                     lengths=lengths + 1)
        return _lm_logits(params, cfg, x)[:, 0], cache

    if cfg.family == "hybrid":
        G = cfg.num_layers // cfg.attn_every
        angles = rope.rope_angles(lengths[:, None], cfg.head_dim, cfg.rope_theta)
        mamba_stacked = jax.tree.map(
            lambda a: a.reshape(G, cfg.attn_every, *a.shape[1:]),
            params["mamba_layers"],
        )
        conv = cache["conv"].reshape(G, cfg.attn_every, *cache["conv"].shape[1:])
        ssm_st = cache["ssm"].reshape(G, cfg.attn_every, *cache["ssm"].shape[1:])
        shared = params["shared"]

        def group_body(x, inp):
            mp, gain, conv_g, ssm_g, kc, vc = inp

            def mamba_body(x, minp):
                lp, cs, hs = minp
                y, nc = ssm.mamba2_block(
                    lp, x, head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state,
                    cache={"conv": cs, "ssm": hs},
                )
                return y, (nc["conv"], nc["ssm"])

            x, (ncs, nhs) = _scan_layers(mamba_body, x, (mp, conv_g, ssm_g), unroll=cfg.unroll_layers)
            xg = x * gain
            h, kn, vn = _self_attention_decode(
                shared["attn"], cfg,
                layers.rms_norm(xg, shared["ln1"], cfg.norm_eps),
                angles, kc, vc, lengths, window=cfg.attn_window,
            )
            y = xg + h
            h, _ = _ffn(shared, cfg, layers.rms_norm(y, shared["ln2"], cfg.norm_eps))
            return y + h, (ncs, nhs, kn, vn)

        x, (ncs, nhs, kn, vn) = _scan_layers(
            group_body, x,
            (mamba_stacked, params["group_gain"], conv, ssm_st,
             cache["k"], cache["v"]),
            unroll=cfg.unroll_layers,
        )
        L = cfg.num_layers
        cache = dict(
            cache,
            conv=ncs.reshape(L, *ncs.shape[2:]).astype(cache["conv"].dtype),
            ssm=nhs.reshape(L, *nhs.shape[2:]),
        )
        cache = _append_kv(cache, kn, vn, lengths, uniform_lengths)
        return _lm_logits(params, cfg, x)[:, 0], cache

    if cfg.family == "audio":
        pe = _sinusoid(cache["k"].shape[2], cfg.d_model).astype(x.dtype)
        x = x + jnp.take(pe, lengths, axis=0)[:, None]

        def body(x, inp):
            lp, kc, vc, xk, xv = inp
            h, kn, vn = _self_attention_decode(
                lp["attn"], cfg,
                layers.rms_norm(x, lp["ln1"], cfg.norm_eps),
                None, kc, vc, lengths, window=None,
            )
            x = x + h
            q, _, _ = _qkv(lp["xattn"], cfg,
                           layers.rms_norm(x, lp["lnx"], cfg.norm_eps), None)
            o = decode_attention(
                q[:, 0], xk, xv,
                jnp.full((x.shape[0],), xk.shape[1], jnp.int32),
            )
            x = x + layers.dense(o.reshape(x.shape[0], 1, -1), lp["xattn"]["wo"])
            h, _ = _ffn(lp, cfg, layers.rms_norm(x, lp["ln2"], cfg.norm_eps))
            return x + h, (kn, vn)

        x, (kn, vn) = _scan_layers(
            body, x,
            (params["dec_layers"], cache["k"], cache["v"], cache["xk"],
             cache["xv"]),
            unroll=cfg.unroll_layers,
        )
        cache = _append_kv(cache, kn, vn, lengths, uniform_lengths)
        return _lm_logits(params, cfg, x)[:, 0], cache

    raise ValueError(cfg.family)
