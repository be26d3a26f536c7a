"""jit'd dispatch wrappers: model-layout adapters over the Pallas kernels.

``use_kernel=None`` picks the Pallas kernel on a TPU backend and the pure-jnp
reference (``ref.py``) elsewhere.  A kernel runs in Pallas interpret mode only
when the caller passes ``interpret=True`` (kernel validation on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .moe_gemm import moe_grouped_gemm
from .rwkv6_chunk import rwkv6_chunk
from . import ref


def _use_kernel(use_kernel: bool | None) -> bool:
    return jax.default_backend() == "tpu" if use_kernel is None else use_kernel


def attention(q_bshd, k_bskd, v_bskd, *, causal: bool = True,
              window: int = 0, use_kernel: bool | None = None,
              interpret: bool = False):
    """Model layout [B,S,H,D] adapter; returns [B,S,H,D]."""
    q = q_bshd.swapaxes(1, 2)
    k = k_bskd.swapaxes(1, 2)
    v = v_bskd.swapaxes(1, 2)
    if _use_kernel(use_kernel):
        out = flash_attention(q, k, v, causal=causal, window=window,
                              interpret=interpret)
    else:
        out = ref.flash_reference(q, k, v, causal=causal, window=window)
    return out.swapaxes(1, 2)


def rwkv_mix(r_bshd, k_bshd, v_bshd, wlog_bshd, u_hd,
             *, use_kernel: bool | None = None, interpret: bool = False):
    """[B,S,H,D] layout adapter for the chunked RWKV6 recurrence."""
    b, s, h, d = r_bshd.shape

    def to_bh(x):
        return x.swapaxes(1, 2).reshape(b * h, s, d)

    u = jnp.broadcast_to(u_hd[None], (b, h, d)).reshape(b * h, d)
    args = (to_bh(r_bshd), to_bh(k_bshd), to_bh(v_bshd), to_bh(wlog_bshd), u)
    if _use_kernel(use_kernel):
        out = rwkv6_chunk(*args, interpret=interpret)
    else:
        out = ref.rwkv6_reference(*args)
    return out.reshape(b, h, s, d).swapaxes(1, 2)


def expert_ffn(buf_ecd, w_edf, *, use_kernel: bool | None = None,
               interpret: bool = False):
    if _use_kernel(use_kernel):
        return moe_grouped_gemm(buf_ecd, w_edf, interpret=interpret)
    return ref.moe_gemm_reference(buf_ecd, w_edf)
