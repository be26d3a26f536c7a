"""Flash attention Pallas TPU kernel (online softmax, VMEM-tiled).

TPU-native schedule: grid = (batch·heads, Sq/bq, Skv/bk); for each query tile
the kv tiles stream through VMEM while running max / normalizer / output
accumulator live in VMEM scratch (f32).  Tile sizes default to MXU-aligned
128×128.  GQA is handled in the kv index map (query head → kv head group), so
K/V tiles are fetched once per group — the memory win that makes GQA decode
fast.  Causal and sliding-window masks are applied with iota comparisons
inside the tile; fully-masked tiles are skipped via ``pl.when`` on the block
index (saves ~half the work for causal).

Validated against ``ref.flash_reference`` in interpret mode (CPU) across
shape/dtype sweeps — see tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, kv_blocks: int, seq_q: int, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Positions as 2-D iotas: Mosaic cannot reshape a 1-D boolean vector
    # into a column, so every mask is built at its final rank.
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, d]
        k = k_ref[0].astype(jnp.float32)                 # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        # Padding beyond the true sequence end.
        mask = k_pos < seq_kv
        if causal:
            offs = seq_kv - seq_q  # queries start at this kv offset
            mask &= k_pos <= q_pos + offs
            if window > 0:
                mask &= (q_pos + offs - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # Zero padded kv rows explicitly: p is ~0 there, but 0 x junk from
        # the padded tile region is NaN-poisonous in the PV product.
        v_row = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, v_ref.shape[1:], 0)
        v = jnp.where(v_row < seq_kv, v_ref[0], 0)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        # Skip tiles strictly above the diagonal (and outside the window).
        offs = seq_kv - seq_q
        first_q = qi * block_q + offs
        last_q = first_q + block_q - 1
        live = ki * block_k <= last_q
        if window > 0:
            live &= (ki + 1) * block_k - 1 >= first_q - window + 1
        pl.when(live)(body)
    else:
        body()

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Skv, D] -> [B, Hq, Sq, D]."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = 1.0 / np.sqrt(d)

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    q_blocks = pl.cdiv(sq, block_q)
    kv_blocks = pl.cdiv(skv, block_k)
    grid = (b * hq, q_blocks, kv_blocks)

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        bb = bh // hq
        h = bh % hq
        return (bb * hkv + h // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_blocks=kv_blocks,
        seq_q=sq, seq_kv=skv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
