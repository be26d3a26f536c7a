"""RWKV6 chunked-recurrence Pallas TPU kernel.

The GPU reference implementation is a per-thread serial scan (CUDA wkv6
kernel); the TPU-native form is *chunkwise*: within a chunk the token
interactions are dense matmuls on the MXU with per-channel decay factors
applied in log space; the cross-chunk state [D,D] (f32) lives in VMEM scratch
and is carried across the sequential chunk grid dimension.

Grid: (B·H, S/C) with the chunk dimension 'arbitrary' (sequential).  Inputs
r,k,v: [BH, S, D]; w = log-decay (≤0) [BH, S, D]; bonus u: [BH, D] (per-head,
broadcast over batch in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
# The separable decay factorization exp(cumW_t)*exp(-cumW_s) is bounded only
# while |cum log-decay| stays within f32 exponent range; 64 steps of the
# fastest realistic RWKV6 decay (~e^-3.3/step) is the safe limit.  Longer
# chunks must be split (the sequence scan handles any S).
MAX_CHUNK = 64


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_scr, *,
                 chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)          # [C, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = w_ref[0].astype(jnp.float32)         # log decay <= 0
    u = u_ref[0].astype(jnp.float32)          # [1, D]

    # Mosaic has no cumsum: prefix sums over the chunk are a matmul with
    # the lower-triangular ones matrix, at full f32 precision.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    exact = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(
        (row >= col).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32)  # logW_t [C, D]
    cum_prev = cum - lw                       # logW_{t-1}
    state = state_scr[...]                    # [D, D]

    # inter-chunk: (r_t ⊙ W_{t-1}) @ S0
    inter = jax.lax.dot_general(
        r * jnp.exp(cum_prev), state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # [C, D]

    # intra-chunk: A[t,s] = Σ_d (r_t W_{t-1}) (k_s / W_s), s < t  (log-safe:
    # both factors bounded by the chunk-local normalization exp(cum - cum)).
    rq = r * jnp.exp(cum_prev)
    ks = k * jnp.exp(-cum)
    att = jax.lax.dot_general(rq, ks, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [C, C]
    att = jnp.where(row > col, att, 0.0)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)    # bonus, s == t
    intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    intra = intra + diag * v

    o_ref[0] = (inter + intra).astype(o_ref.dtype)

    # state update: S1 = W_C ⊙ S0 + Σ_s (k_s W_C / W_s) v_s^T
    wtot = cum[chunk - 1:chunk]                          # [1, D]
    # The same total as a column (decay of the state's rows), summed
    # directly rather than transposed.
    wtot_col = jax.lax.dot_general(
        lw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32)  # [D, 1]
    kdec = k * jnp.exp(wtot - cum)                       # [C, D]
    state_scr[...] = state * jnp.exp(wtot_col) + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def rwkv6_chunk(r, k, v, w_log, u, *, chunk: int = DEFAULT_CHUNK,
                interpret: bool = False):
    """r,k,v,w_log: [BH, S, D]; u: [BH, D].  Returns [BH, S, D] (f32)."""
    bh, s, d = r.shape
    chunk = min(chunk, s)
    assert chunk <= MAX_CHUNK, (
        f"chunk {chunk} > {MAX_CHUNK}: the separable decay form overflows "
        f"f32 for long chunks; split the sequence instead")
    assert s % chunk == 0, (s, chunk)
    grid = (bh, s // chunk)

    def seq_map(b, c):
        return (b, c, 0)

    def u_map(b, c):
        return (b, 0, 0)

    kernel = functools.partial(_rwkv_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), seq_map),
            pl.BlockSpec((1, chunk, d), seq_map),
            pl.BlockSpec((1, chunk, d), seq_map),
            pl.BlockSpec((1, chunk, d), seq_map),
            # [BH, 1, D]: a (1, D) block of a 2-D [BH, D] array breaks the
            # TPU rule that a block's last two dims tile by (8, 128).
            pl.BlockSpec((1, 1, d), u_map),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), seq_map),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w_log, u[:, None, :])
    return out
