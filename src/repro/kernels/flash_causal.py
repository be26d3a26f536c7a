"""Causal flash attention for training: Pallas TPU forward and backward.

q: [B, H, S, D]; k, v: [B, KV, S, D]; query head h reads kv head
h // (H / KV) (grouped-query attention without repeating k/v in HBM).

- Forward, grid (B, H, S/bq, S/bk): logits, running max, normalizer and
  output accumulator live in VMEM (f32); the output and the per-row
  log-sum-exp are written once per query tile.
- Backward, one kernel, grid (B, KV, S/bk, G * S/bq), recomputes the
  probabilities from q, k and the saved log-sum-exp.  dk and dv accumulate
  in VMEM over the G query heads of the group and their query tiles, so a
  kv head's gradient is summed on chip; dq is written as one f32 share per
  key tile and the shares are summed in f32.
- Tiles strictly above the diagonal are skipped in both kernels: their
  compute is not run and their input index maps repeat a live tile, so
  no DMA is issued for them.  Only tiles that cross the diagonal build a
  mask.

Numerics follow ``models/layers._sdpa_blocked``: q·kᵀ accumulates in f32
and is scaled in f32, softmax statistics are f32, probabilities (and
dS, scaled) enter the MXU in the input dtype with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


def _last_kv_block(i, bq: int, bk: int):
    """Last key tile that query tile ``i`` attends to."""
    return ((i + 1) * bq - 1) // bk


def _first_q_block(j, bq: int, bk: int):
    """First query tile that attends to key tile ``j``."""
    return (j * bk) // bq


def _tile_state(i, j, bq: int, bk: int):
    """(live, below): the tile has a causal entry; it has no masked one."""
    live = j * bk <= (i + 1) * bq - 1
    below = (j + 1) * bk - 1 <= i * bq
    return live, below


def _causal(i, j, shape, q_axis: int):
    """Boolean causal mask of a tile, q positions along ``q_axis``."""
    bq, bk = (shape[0], shape[1]) if q_axis == 0 else (shape[1], shape[0])
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = j * bk + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return k_pos <= q_pos


def _lanes(x, n: int):
    """[rows, 128] lane-replicated statistic -> [rows, n]."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return x[:, :n]


def _run_tiles(i, j, bq: int, bk: int, step):
    live, below = _tile_state(i, j, bq, bk)
    pl.when(below)(functools.partial(step, False))
    pl.when(live & jnp.logical_not(below))(functools.partial(step, True))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, bq: int, bk: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_causal(i, j, s.shape, 0), s, MASK_VALUE)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        v = v_ref[...]
        acc_scr[...] = (_lanes(alpha, acc_scr.shape[1]) * acc_scr[...]
                        + lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))

    _run_tiles(i, j, bq, bk, step)

    @pl.when(j == _last_kv_block(i, bq, bk))
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / _lanes(l, acc_scr.shape[1])).astype(
            o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _forward(q, k, v, scale, bq, bk, interpret):
    b, h, s, d = q.shape
    group = h // k.shape[1]
    nq, nk = s // bq, s // bk

    def q_map(bi, hi, i, j):
        return bi, hi, i, 0

    def kv_map(bi, hi, i, j):
        return bi, hi // group, jnp.minimum(j, _last_kv_block(i, bq, bk)), 0

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk),
        grid=(b, h, nq, nk),
        in_specs=[pl.BlockSpec((None, None, bq, d), q_map),
                  pl.BlockSpec((None, None, bk, d), kv_map),
                  pl.BlockSpec((None, None, bk, d), kv_map)],
        out_specs=[pl.BlockSpec((None, None, bq, d), q_map),
                   pl.BlockSpec((None, None, bq, LANES), q_map)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_causal_fwd",
    )(q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, di_ref, dq_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale: float, bq: int, bk: int,
                nq: int):
    j, t = pl.program_id(2), pl.program_id(3)
    i = t % nq

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        # Key rows, query columns: the row statistics are lane vectors.
        q, do, k = q_ref[...], do_ref[...], k_ref[...]
        st = lax.dot_general(k, q, _NT,
                             preferred_element_type=jnp.float32) * scale
        if masked:
            st = jnp.where(_causal(i, j, st.shape, 1), st, MASK_VALUE)
        pt = jnp.exp(st - lse_ref[...])
        dv_scr[...] += lax.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...]) * scale
        dk_scr[...] += lax.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)
        dq_ref[...] = lax.dot(dst.T.astype(k.dtype), k,
                              preferred_element_type=jnp.float32)

    _run_tiles(i, j, bq, bk, step)

    @pl.when(jnp.logical_not(_tile_state(i, j, bq, bk)[0]))
    def _no_keys():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _backward(q, k, v, out, lse, do, scale, bq, bk, interpret):
    """One pass over (kv tile, query head of its group, query tile): dk and
    dv accumulate in VMEM; each visit writes its f32 share of dq, and the
    shares of the key tiles are summed afterwards."""
    b, h, s, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    nq, nk = s // bq, s // bk
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    def kv_map(bi, ki, j, t):
        return bi, ki, j, 0

    def head(ki, t):
        return ki * group + t // nq

    def live_tile(j, t):         # a skipped tile repeats the first live one
        return jnp.maximum(t % nq, _first_q_block(j, bq, bk))

    def qd_map(bi, ki, j, t):
        return bi, head(ki, t), live_tile(j, t), 0

    def row_map(bi, ki, j, t):
        return bi, head(ki, t), 0, live_tile(j, t)

    def dq_map(bi, ki, j, t):
        return j, bi, head(ki, t), t % nq, 0

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bk=bk, nq=nq),
        grid=(b, kvh, nk, group * nq),
        in_specs=[pl.BlockSpec((None, None, bk, d), kv_map),
                  pl.BlockSpec((None, None, bk, d), kv_map),
                  pl.BlockSpec((None, None, bq, d), qd_map),
                  pl.BlockSpec((None, None, bq, d), qd_map),
                  pl.BlockSpec((None, None, 1, bq), row_map),
                  pl.BlockSpec((None, None, 1, bq), row_map)],
        out_specs=[pl.BlockSpec((None, None, None, bq, d), dq_map),
                   pl.BlockSpec((None, None, bk, d), kv_map),
                   pl.BlockSpec((None, None, bk, d), kv_map)],
        out_shape=[jax.ShapeDtypeStruct((nk, *q.shape), jnp.float32),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_causal_bwd",
    )(k, v, q, do, lse[:, :, None, :], di[:, :, None, :])
    return dq.sum(axis=0).astype(q.dtype), dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, scale, bq, bk, interpret):
    return _forward(q, k, v, scale, bq, bk, interpret)[0]


def _attention_fwd(q, k, v, scale, bq, bk, interpret):
    out, lse = _forward(q, k, v, scale, bq, bk, interpret)
    return out, (q, k, v, out, lse)


def _attention_bwd(scale, bq, bk, interpret, res, do):
    q, k, v, out, lse = res
    return _backward(q, k, v, out, lse, do, scale, bq, bk, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def fits(s: int, d: int, block_q: int, block_k: int) -> bool:
    """The kernel covers ``s`` positions of head size ``d`` with these tiles
    (capped at ``s``): each divides ``s`` and is a multiple of the 128-wide
    lane or ``s`` itself, and ``d`` is at most 128 or a multiple of it."""
    tiles = (min(block_q, s), min(block_k, s))
    return (all(s % t == 0 and (t % LANES == 0 or t == s) for t in tiles)
            and (d <= LANES or d % LANES == 0))


def causal_flash_attention(q, k, v, *, scale: float, block_q: int,
                           block_k: int, interpret: bool = False):
    """q: [B, H, S, D]; k/v: [B, KV, S, D] -> [B, H, S, D], causal, for
    shapes the tiles ``fits``.  ``interpret`` runs the kernels in Pallas
    interpret mode (validation on the CPU)."""
    s, d = q.shape[2], q.shape[3]
    if not fits(s, d, block_q, block_k) or q.shape[1] % k.shape[1]:
        raise ValueError(f"untileable attention: q {q.shape}, k {k.shape}, "
                         f"tiles {block_q}x{block_k}")
    return _attention(q, k, v, float(scale), min(block_q, s),
                      min(block_k, s), interpret)
