"""Unit tests: layers, rope, attention chunking, MoE dispatch, SSM, training."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, smoke_variant
from repro.models import forward, init_params
from repro.models.attention import attention, attention_reference
from repro.models.layers import rms_norm
from repro.models.moe import capacity, moe_ffn, init_moe, route
from repro.models.rope import (
    apply_rotary,
    mrope_angles,
    positions_default,
    rope_angles,
)
from repro.models.ssm import (
    init_mamba2_layer,
    init_rwkv6_layer,
    mamba2_block,
    rwkv6_block,
)

KEY = jax.random.PRNGKey


# -- attention chunking ---------------------------------------------------------


@pytest.mark.parametrize("S,block", [(64, 16), (60, 16), (128, 128)])
@pytest.mark.parametrize("window", [None, 13])
def test_chunked_attention_matches_reference(S, block, window):
    B, H, K, dh = 2, 4, 2, 16
    q = jax.random.normal(KEY(0), (B, S, H, dh))
    k = jax.random.normal(KEY(1), (B, S, K, dh))
    v = jax.random.normal(KEY(2), (B, S, K, dh))
    o = attention(q, k, v, causal=True, window=window, block_kv=block)
    r = attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5,
                               rtol=2e-5)


def test_attention_kv_lengths_mask():
    B, S, H, K, dh = 2, 32, 2, 2, 8
    q = jax.random.normal(KEY(3), (B, 1, H, dh))
    k = jax.random.normal(KEY(4), (B, S, K, dh))
    v = jax.random.normal(KEY(5), (B, S, K, dh))
    lengths = jnp.array([5, 32], jnp.int32)
    o = attention(q, k, v, causal=False, kv_lengths=lengths,
                  q_offset=lengths - 1, block_kv=8)
    # manually truncate: request 0 must only see the first 5 kv entries
    o_trunc = attention(q[:1], k[:1, :5], v[:1, :5], causal=False,
                        q_offset=jnp.array([4]), block_kv=8)
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(o_trunc[0]),
                               atol=1e-5, rtol=1e-5)


# -- rope -------------------------------------------------------------------------


def test_rope_rotation_preserves_norm():
    x = jax.random.normal(KEY(6), (2, 8, 4, 32))
    ang = rope_angles(positions_default(2, 8), 32, 1e4)
    y = apply_rotary(x, ang)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5,
    )


def test_rope_relative_property():
    """<q_m, k_n> depends only on m - n."""
    dh = 16
    q = jax.random.normal(KEY(7), (1, 1, 1, dh))
    k = jax.random.normal(KEY(8), (1, 1, 1, dh))

    def dot_at(m, n):
        qa = apply_rotary(q, rope_angles(jnp.array([[m]]), dh, 1e4))
        ka = apply_rotary(k, rope_angles(jnp.array([[n]]), dh, 1e4))
        return float(jnp.sum(qa * ka))

    assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4
    assert abs(dot_at(7, 0) - dot_at(17, 10)) < 1e-4


def test_mrope_text_equals_rope():
    """Identical t/h/w ids (text tokens) must reduce to plain RoPE."""
    B, S, hd = 2, 6, 32
    pos = positions_default(B, S)
    a1 = rope_angles(pos, hd, 1e4)
    a2 = mrope_angles(jnp.stack([pos, pos, pos]), hd, 1e4, (4, 6, 6))
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-6)


# -- moe ---------------------------------------------------------------------------


def test_moe_capacity_monotone():
    assert capacity(1024, 8, 2, 1.25) >= capacity(1024, 8, 2, 1.0)
    assert capacity(1024, 8, 2, 1.25) % 8 == 0


def test_moe_route_normalized():
    p = init_moe(KEY(9), 32, 64, 8, jnp.float32)
    x = jax.random.normal(KEY(10), (16, 32))
    gates, idx, aux = route(p["router"], x, 2)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert int(idx.max()) < 8 and float(aux) > 0


def test_moe_ffn_matches_dense_per_expert():
    """With ample capacity, MoE == per-token dense mix of chosen experts."""
    E, D, F, T = 4, 16, 32, 8
    p = init_moe(KEY(11), D, F, E, jnp.float32)
    x = jax.random.normal(KEY(12), (1, T, D))
    out, aux = moe_ffn(p, x, experts_per_token=2, capacity_factor=8.0)
    gates, idx, _ = route(p["router"], x[0], 2)

    def expert_fwd(e, v):
        h = jax.nn.silu(v @ p["wi_gate"][e]) * (v @ p["wi_up"][e])
        return h @ p["wo"][e]

    want = np.zeros((T, D), np.float32)
    for t in range(T):
        for j in range(2):
            want[t] += float(gates[t, j]) * np.asarray(
                expert_fwd(int(idx[t, j]), x[0, t])
            )
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=1e-4, rtol=1e-4)


def test_moe_capacity_drops_tokens():
    """With capacity_factor -> tiny, some tokens must be dropped (output 0)."""
    E, D, F, T = 2, 8, 16, 64
    p = init_moe(KEY(13), D, F, E, jnp.float32)
    x = jax.random.normal(KEY(14), (1, T, D))
    out_full, _ = moe_ffn(p, x, experts_per_token=1, capacity_factor=8.0)
    out_tiny, _ = moe_ffn(p, x, experts_per_token=1, capacity_factor=0.1)
    # tiny capacity zeroes most rows
    zero_rows = np.sum(np.all(np.abs(np.asarray(out_tiny[0])) < 1e-9, axis=-1))
    assert zero_rows > T // 2


# -- ssm ----------------------------------------------------------------------------


def test_rwkv6_block_streaming_equals_batch():
    """Running T steps through the cache == one full-sequence pass."""
    D, F, hd = 32, 64, 16
    p = init_rwkv6_layer(KEY(15), D, F, hd, jnp.float32)
    B, T = 1, 6
    x = jax.random.normal(KEY(16), (B, T, D)) * 0.5
    y_full, _ = rwkv6_block(p, x, hd)
    cache = None
    ys = []
    for t in range(T):
        y, cache = rwkv6_block(p, x[:, t:t + 1], hd, cache=cache)
        ys.append(y)
    y_stream = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_stream),
                               atol=2e-4, rtol=2e-3)


def test_mamba2_block_streaming_equals_batch():
    D, di, S, hd = 32, 64, 16, 16
    p = init_mamba2_layer(KEY(17), D, di, S, hd, jnp.float32)
    B, T = 1, 6
    x = jax.random.normal(KEY(18), (B, T, D)) * 0.5
    y_full, _ = mamba2_block(p, x, head_dim=hd, ssm_state=S)
    cache = {"conv": jnp.zeros((B, 3, di + 2 * S)),
             "ssm": jnp.zeros((B, di // hd, hd, S))}
    ys = []
    for t in range(T):
        y, cache = mamba2_block(p, x[:, t:t + 1], head_dim=hd, ssm_state=S,
                                cache=cache)
        ys.append(y)
    y_stream = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_stream),
                               atol=2e-4, rtol=2e-3)


# -- misc ---------------------------------------------------------------------------


def test_rms_norm_scale_invariant_direction():
    x = jax.random.normal(KEY(19), (4, 32))
    w = jnp.ones((32,))
    y1 = rms_norm(x, w)
    y2 = rms_norm(3.0 * x, w)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_unroll_equals_scan():
    cfg = smoke_variant(get_arch("qwen3-8b"))
    params = init_params(cfg, KEY(20))
    batch = {"tokens": jax.random.randint(KEY(21), (2, 8), 0, cfg.vocab_size)}
    a = forward(params, cfg, batch)["logits"]
    b = forward(params, dataclasses.replace(cfg, unroll_layers=True),
                batch)["logits"]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


def test_sliding_window_decode_slices_cache():
    """Windowed decode (gather path) == full-cache decode with window mask."""
    import repro.models.transformer as T

    cfg = dataclasses.replace(smoke_variant(get_arch("mixtral-8x7b")),
                              attn_window=8)
    params = init_params(cfg, KEY(22))
    tokens = jax.random.randint(KEY(23), (2, 12), 0, cfg.vocab_size)
    from repro.models import decode_step, prefill

    # max_seq 64 > 2*window triggers the gather path
    lg, cache = prefill(params, cfg, {"tokens": tokens}, max_seq=64)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    l1, _ = decode_step(params, cfg, cache, tok)
    # force the mask path by shrinking max_seq below 2*window
    lg2, cache2 = prefill(params, cfg, {"tokens": tokens}, max_seq=14)
    l2, _ = decode_step(params, cfg, cache2, tok)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-3,
                               rtol=2e-3)


@pytest.mark.parametrize("case", ["ragged", "uniform", "window"])
def test_decode_writes_each_slot_in_place(case):
    """Four slots prefilled to their own lengths and inserted as continuous
    batching inserts them: each decode step matches a full forward over the
    slot's tokens, and writes exactly the new k/v at the slot's old length,
    leaving every other cache entry as it was."""
    from repro.models import decode_step, init_cache, prefill

    if case == "window":  # max_seq 64 > 2 * window: the gathered-window path
        cfg = dataclasses.replace(smoke_variant(get_arch("mixtral-8x7b")),
                                  attn_window=8)
        max_seq = 64
    else:
        cfg, max_seq = smoke_variant(get_arch("qwen3-8b")), 32
    lens = [7, 7, 7, 7] if case == "uniform" else [5, 12, 3, 9]
    params = init_params(cfg, KEY(29))
    cache = init_cache(cfg, len(lens), max_seq)
    seqs = []
    for slot, n in enumerate(lens):
        prompt = jax.random.randint(KEY(30 + slot), (1, n), 0, cfg.vocab_size)
        lg, sub = prefill(params, cfg, {"tokens": prompt}, max_seq)
        cache = jax.tree.map(
            lambda leaf, s: (leaf.at[slot].set(s[0]) if leaf.ndim == 1
                             else leaf.at[:, slot].set(s[:, 0])),
            cache, sub)
        seqs.append([int(t) for t in prompt[0]] + [int(jnp.argmax(lg[0]))])

    for _ in range(3):
        old = cache
        logits, cache = decode_step(
            params, cfg, old, jnp.asarray([s[-1] for s in seqs], jnp.int32),
            uniform_lengths=case == "uniform")
        for b, seq in enumerate(seqs):
            n = len(seq) - 1          # the cache holds all but the last token
            assert int(old["lengths"][b]) == n
            assert int(cache["lengths"][b]) == n + 1
            ref_logits, ref = prefill(params, cfg,
                                      {"tokens": jnp.asarray(seq)[None]},
                                      max_seq)
            np.testing.assert_allclose(np.asarray(logits[b]),
                                       np.asarray(ref_logits[0]),
                                       atol=2e-2, rtol=2e-2)
            for name in ("k", "v"):
                was = np.asarray(old[name][:, b], np.float32)
                now = np.asarray(cache[name][:, b], np.float32)
                np.testing.assert_allclose(
                    now[:, n], np.asarray(ref[name][:, 0, n], np.float32),
                    atol=2e-2, rtol=2e-2)
                np.testing.assert_array_equal(np.delete(now, n, axis=1),
                                              np.delete(was, n, axis=1))
        seqs = [s + [int(t)] for s, t in zip(seqs, jnp.argmax(logits, -1))]


# -- Mamba2: the chunked scan (prefill) against the recurrence (decode) ----------


@pytest.mark.parametrize("T,chunk", [(8, 8), (37, 8), (64, 16), (5, 16)])
@pytest.mark.parametrize("carried", [False, True])
def test_ssd_chunked_matches_the_recurrence(T, chunk, carried):
    """Whole chunks, a ragged tail, a sequence shorter than a chunk; from a
    zero state and from a state carried in."""
    from repro.models.ssm import ssd_chunked, ssd_scan

    B, nh, hd, S = 2, 3, 4, 5
    k = jax.random.split(KEY(40), 6)
    x = jax.random.normal(k[0], (B, T, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, nh)))
    A = -jnp.exp(jax.random.normal(k[2], (nh,)))
    Bs = jax.random.normal(k[3], (B, T, S))
    Cs = jax.random.normal(k[4], (B, T, S))
    h0 = (jax.random.normal(k[5], (B, nh, hd, S)) if carried
          else jnp.zeros((B, nh, hd, S)))
    y_seq, h_seq = ssd_scan(x, dt, A, Bs, Cs, h0)
    y, h = ssd_chunked(x, dt, A, Bs, Cs, h0, chunk)
    # float32 sums in another order; outputs reach ~30
    np.testing.assert_allclose(y, y_seq, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(h, h_seq, atol=1e-4, rtol=1e-5)


def test_granite_slot_3_of_4_decodes_as_alone():
    """A request admitted into slot 3 while slots 0-2 are mid-decode, through
    the policy's own insert (K/V, then recurrent state), decodes the logits
    it decodes alone in a cache of one slot."""
    from repro.models import decode_step, init_cache, prefill
    from repro.serving.scheduler import ContinuousBatchPolicy

    cfg, max_seq = smoke_variant(get_arch("granite-4.0-h-micro")), 48
    params = init_params(cfg, KEY(50))
    step = jax.jit(lambda c, t: decode_step(params, cfg, c, t))
    prompts = [jax.random.randint(KEY(51 + i), (1, n), 0, cfg.vocab_size)
               for i, n in enumerate((9, 20, 13, 17))]

    class Core:
        counters = {"state_bytes_inserted": 0}

    policy = ContinuousBatchPolicy(4, max_seq)
    policy.kv = init_cache(cfg, 4, max_seq)
    toks = [0] * 4
    for s, prompt in enumerate(prompts[:3]):
        lg, sub = prefill(params, cfg, {"tokens": prompt}, max_seq)
        policy._insert(Core, sub, s, rid=s)
        toks[s] = int(jnp.argmax(lg[0]))
    for _ in range(2):
        lg, policy.kv = step(policy.kv, jnp.asarray(toks, jnp.int32))
        toks = [int(t) for t in jnp.argmax(lg, -1)]
    lg, sub = prefill(params, cfg, {"tokens": prompts[3]}, max_seq)
    policy._insert(Core, sub, 3, rid=3)
    toks[3] = int(jnp.argmax(lg[0]))
    alone, tok = sub, jnp.asarray([toks[3]], jnp.int32)
    for _ in range(4):
        lg, policy.kv = step(policy.kv, jnp.asarray(toks, jnp.int32))
        ref, alone = step(alone, tok)
        # float32; only the batch differs
        np.testing.assert_allclose(lg[3], ref[0], atol=1e-5, rtol=1e-5)
        toks = [int(t) for t in jnp.argmax(lg, -1)]
        tok = jnp.argmax(ref, -1).astype(jnp.int32)
    assert Core.counters["state_bytes_inserted"] == 4 * sum(
        a.nbytes for k, a in sub.items() if k in ("conv", "ssm"))
