"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.int8_matmul import quantize_int8

KEY = jax.random.PRNGKey


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize("B,H,K,S,dh", [
    (1, 2, 1, 32, 16),
    (2, 4, 2, 64, 32),
    (1, 8, 8, 128, 64),   # MHA
    (2, 6, 2, 96, 32),    # non-pow2 seq with padding blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 17])
def test_flash_attention_sweep(B, H, K, S, dh, dtype, window):
    q = jax.random.normal(KEY(0), (B, H, S, dh), dtype)
    k = jax.random.normal(KEY(1), (B, K, S, dh), dtype)
    v = jax.random.normal(KEY(2), (B, K, S, dh), dtype)
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            block_q=32, block_kv=32)
    r = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("B,K,G,S,dh", [
    (1, 1, 4, 64, 32),
    (2, 2, 4, 128, 32),
    (3, 4, 1, 96, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, K, G, S, dh, dtype):
    q = jax.random.normal(KEY(3), (B, K, G, dh), dtype)
    kc = jax.random.normal(KEY(4), (B, K, S, dh), dtype)
    vc = jax.random.normal(KEY(5), (B, K, S, dh), dtype)
    lengths = jnp.arange(B, dtype=jnp.int32) * 17 % S + 1
    o = ops.decode_attention(q, kc, vc, lengths, block_s=32)
    r = ref.decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32), **_tol(dtype)
    )


def test_kernels_interpret_only_on_cpu(monkeypatch):
    """A backend that is neither the TPU nor the CPU is an error, never a
    silent interpreter."""
    assert ops._default_interpret() is True       # the tests run on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="neither"):
        ops._default_interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._default_interpret() is False


def test_decode_attention_window():
    B, K, G, S, dh = 2, 2, 2, 128, 32
    q = jax.random.normal(KEY(6), (B, K, G, dh))
    kc = jax.random.normal(KEY(7), (B, K, S, dh))
    vc = jax.random.normal(KEY(8), (B, K, S, dh))
    lengths = jnp.array([100, 128], jnp.int32)
    o = ops.decode_attention(q, kc, vc, lengths, window=16, block_s=32)
    r = ref.decode_attention_ref(q, kc, vc, lengths, window=16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("E,C,D,F", [(2, 32, 64, 48), (4, 64, 96, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_sweep(E, C, D, F, dtype):
    x = jax.random.normal(KEY(9), (E, C, D), dtype)
    w = jax.random.normal(KEY(10), (E, D, F), dtype)
    gs = (jnp.arange(E, dtype=jnp.int32) * 13) % (C + 1)
    o = ops.moe_gmm(x, w, gs, block_c=16, block_f=32, block_d=32)
    r = ref.moe_gmm_ref(x, w, gs)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32), atol=5e-2
        if dtype == jnp.bfloat16 else 1e-4, rtol=5e-2
        if dtype == jnp.bfloat16 else 1e-4,
    )


@pytest.mark.parametrize("M,D,N", [(16, 64, 32), (48, 128, 64)])
def test_int8_matmul_sweep(M, D, N):
    x = jax.random.normal(KEY(11), (M, D))
    w = jax.random.normal(KEY(12), (D, N))
    wq, sc = quantize_int8(w)
    o = ops.int8_matmul(x, wq, sc, block_m=16, block_n=16, block_d=32)
    r = ref.int8_matmul_ref(x, wq, sc)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-3,
                               rtol=1e-3)
    # quantization error vs full precision stays small
    full = np.asarray(x @ w)
    rel = np.abs(np.asarray(o) - full).mean() / np.abs(full).mean()
    assert rel < 0.02, rel


@pytest.mark.parametrize("B,H,T,dh", [(1, 2, 32, 16), (2, 3, 48, 32)])
@pytest.mark.parametrize("chunk", [8, 16])
def test_rwkv6_scan_sweep(B, H, T, dh, chunk):
    r_ = jax.random.normal(KEY(13), (B, H, T, dh)) * 0.5
    k_ = jax.random.normal(KEY(14), (B, H, T, dh)) * 0.5
    v_ = jax.random.normal(KEY(15), (B, H, T, dh)) * 0.5
    w_ = jax.nn.sigmoid(jax.random.normal(KEY(16), (B, H, T, dh)))
    u_ = jax.random.normal(KEY(17), (H, dh)) * 0.3
    s0 = jax.random.normal(KEY(18), (B, H, dh, dh)) * 0.1
    o, sf = ops.rwkv6_scan(r_, k_, v_, w_, u_, s0, chunk=chunk)
    orf, sfr = ref.rwkv6_scan_ref(r_, k_, v_, w_, u_, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sfr), atol=2e-4,
                               rtol=2e-4)


def test_rwkv6_kernel_matches_model_layer():
    """Kernel agrees with the model's own recurrence (ssm.rwkv6_wkv_step)."""
    from repro.models.ssm import rwkv6_wkv_step

    B, H, T, dh = 1, 2, 16, 8
    r_ = jax.random.normal(KEY(19), (B, H, T, dh)) * 0.5
    k_ = jax.random.normal(KEY(20), (B, H, T, dh)) * 0.5
    v_ = jax.random.normal(KEY(21), (B, H, T, dh)) * 0.5
    w_ = jax.nn.sigmoid(jax.random.normal(KEY(22), (B, H, T, dh)))
    u_ = jax.random.normal(KEY(23), (H, dh)) * 0.3
    s = jnp.zeros((B, H, dh, dh))
    outs = []
    for t in range(T):
        s, o = rwkv6_wkv_step(s, r_[:, :, t], k_[:, :, t], v_[:, :, t],
                              w_[:, :, t], u_)
        outs.append(o)
    model_out = jnp.stack(outs, axis=2)
    kern_out, _ = ops.rwkv6_scan(r_, k_, v_, w_, u_,
                                 jnp.zeros((B, H, dh, dh)), chunk=8)
    np.testing.assert_allclose(np.asarray(kern_out), np.asarray(model_out),
                               atol=2e-4, rtol=2e-4)
