"""Pallas TPU grouped (expert) matmul: the MoE FFN hot spot.

Computes out[e] = x[e] @ w[e] for E experts with a 4-D grid
(experts, row-blocks, col-blocks, contraction-blocks) accumulating in a VMEM
f32 scratch tile.  ``group_sizes`` masks rows beyond each expert's live token
count so padded capacity slots contribute zeros (and on real TPU the mask also
lets the compiler skip dead MXU passes on fully-empty tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(gs_ref, x_ref, w_ref, o_ref, acc_scr, *, block_c: int):
    ci = pl.program_id(1)
    di = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)                     # (bc, bd)
    rows = ci * block_c + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    x = jnp.where(rows < gs_ref[pl.program_id(0)], x, 0.0)
    acc_scr[...] += jax.lax.dot(
        x, w_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )

    @pl.when(di == nd - 1)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def moe_gmm(
    x, w, group_sizes=None, *, block_c: int = 128, block_f: int = 128,
    block_d: int = 256, interpret: bool = False,
):
    """x: (E, C, D); w: (E, D, F); group_sizes: (E,) live rows per expert."""
    E, C, D = x.shape
    F = w.shape[2]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)
    if group_sizes is None:
        group_sizes = jnp.full((E,), C, jnp.int32)
    kernel = functools.partial(_kernel, block_c=block_c)
    return pl.pallas_call(
        kernel,
        grid=(E, pl.cdiv(C, block_c), pl.cdiv(F, block_f), pl.cdiv(D, block_d)),
        in_specs=[
            # whole in SMEM: a (1, 1) VMEM block is below the (8, 128) tiling
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_c, block_d), lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((1, block_d, block_f), lambda e, ci, fi, di: (e, di, fi)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_c, block_f), lambda e, ci, fi, di: (e, ci, fi)
        ),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(group_sizes.reshape(E).astype(jnp.int32), x, w)
