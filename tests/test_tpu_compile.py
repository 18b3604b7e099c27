"""Compile the serving path for a described TPU v5e, with no chip attached.

XLA's TPU compiler ships with JAX and compiles for a chip that is described
rather than attached.  It refuses what the chip would refuse: blocks below
the (8, 128) tiling, too much VMEM, a program larger than HBM.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.decode_attention import decode_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.moe_gmm import moe_gmm
from repro.models import decode_step, init_cache, init_params, prefill

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache

        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on_chip(sharding, tree):
    return jax.tree.map(lambda s: _shape(sharding, s.shape, s.dtype), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# minitron-4b's int8 matmuls: decode (4 slots x qkv), prefill (wo), MLP down
@pytest.mark.parametrize("M,D,N", [(4, 3072, 9216), (56, 3072, 5120),
                                   (200, 9216, 3072)])
def test_int8_matmul_compiles(one_chip, M, D, N):
    compiled = _compile(
        functools.partial(int8_matmul, interpret=False),
        _shape(one_chip, (M, D), jnp.bfloat16),
        _shape(one_chip, (D, N), jnp.int8),
        _shape(one_chip, (N,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles(one_chip):
    B, K, G, S, hd = 8, 8, 3, 4096, 128      # minitron-4b GQA at 4k context
    compiled = _compile(
        functools.partial(decode_attention, interpret=False),
        _shape(one_chip, (B, K, G, hd), jnp.bfloat16),
        _shape(one_chip, (B, K, S, hd), jnp.bfloat16),
        _shape(one_chip, (B, K, S, hd), jnp.bfloat16),
        _shape(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_gmm_compiles(one_chip):
    E, C, D, F = 8, 256, 4096, 14336         # mixtral-8x7b expert FFN
    compiled = _compile(
        functools.partial(moe_gmm, interpret=False),
        _shape(one_chip, (E, C, D), jnp.bfloat16),
        _shape(one_chip, (E, D, F), jnp.bfloat16),
        _shape(one_chip, (E,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("step", ["prefill", "decode_step"])
def test_minitron_4b_step_fits_one_chip(one_chip, step):
    """Full-width bf16 minitron-4b, 8 slots of 256 positions, 16-token
    prompts: weights, cache and temporaries fit one v5e's HBM."""
    cfg = get_arch("minitron-4b")
    B, S, max_seq = 8, 16, 256

    params = _on_chip(one_chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    if step == "prefill":
        fn = jax.jit(lambda p, t: prefill(p, cfg, {"tokens": t}, max_seq))
        args = (params, _shape(one_chip, (B, S), jnp.int32))
    else:
        fn = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t),
                     donate_argnums=(1,))
        cache = _on_chip(one_chip, jax.eval_shape(
            lambda: init_cache(cfg, B, max_seq)))
        args = (params, cache, _shape(one_chip, (B,), jnp.int32))
    mem = fn.lower(*args).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 8e9     # the weights are all there
    assert total < V5E_HBM_BYTES, total


def test_minitron_4b_decode_writes_kv_in_place(one_chip):
    """The donated decode step at 8 slots of 2048 positions writes each new
    token's k/v into the donated slab: no second slab in temporaries, and no
    slab-sized copy or dynamic-update-slice."""
    cfg = get_arch("minitron-4b")
    B, max_seq = 8, 2048

    params = _on_chip(one_chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = _on_chip(one_chip, jax.eval_shape(
        lambda: init_cache(cfg, B, max_seq)))
    fn = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t),
                 donate_argnums=(1,))
    compiled = fn.lower(params, cache,
                        _shape(one_chip, (B,), jnp.int32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2**20, temp
    slab = "bf16[%s]" % ",".join(map(str, cache["k"].shape))
    assert cache["k"].shape == (32, B, max_seq, 8, 128)
    writes = re.findall(r"%(\S+) = " + re.escape(slab) + r"\{[^}]*\} (\S+)\(",
                        compiled.as_text())
    assert writes, "no slab-shaped instruction found"
    bad = [(name, op) for name, op in writes
           if op in ("copy", "dynamic-update-slice")
           or (op == "fusion" and ("copy" in name
                                   or "dynamic-update-slice" in name))]
    assert not bad, bad
