"""Grouped (expert-blocked) GEMM Pallas TPU kernel for MoE dispatch buffers.

MegaBlocks' insight (block-sparse expert matmuls) re-tiled for the TPU MXU:
after sort-based dispatch packs tokens into equal-capacity expert buffers
[E, C, d], the expert FFN is a block-diagonal matmul.  The kernel walks
grid = (E, C/bc, F/bf, d/bd) with the contraction dim innermost, accumulating
in VMEM scratch — each expert's weight tile is fetched once per (bc, bf) tile
pair, giving the same data-reuse schedule as a dense GEMM per expert without
materializing a [E·C, d] × [E·d, F] dense product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_gemm_kernel(x_ref, w_ref, o_ref, acc_scr, *, k_blocks: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == k_blocks - 1)
    def _finish():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret"))
def moe_grouped_gemm(x, w, *, block_c: int = 128, block_f: int = 128,
                     block_d: int = 512, interpret: bool = False):
    """x: [E, C, d] expert buffers; w: [E, d, F] -> [E, C, F]."""
    e, c, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert c % block_c == 0 and f % block_f == 0 and d % block_d == 0
    k_blocks = d // block_d
    grid = (e, c // block_c, f // block_f, k_blocks)

    out = pl.pallas_call(
        functools.partial(_moe_gemm_kernel, k_blocks=k_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda ei, ci, fi, ki: (ei, ci, ki)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda ei, ci, fi, ki: (ei, ki, fi)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda ei, ci, fi, ki: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out
