"""Composable transformer building blocks (functional, sharding-annotated).

Every block ships a ``*_defs(cfg)`` returning a ParamInfo tree and a
``*_apply(cfg, params, ...)`` pure function.  Attention supports GQA/MQA,
RoPE, causal + sliding-window masks, QKV bias, logit soft-capping, cross
attention, and single-token decode against a KV cache.
"""
from __future__ import annotations

import collections
import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import ParamInfo, current_mesh, shard
from ..kernels import flash_causal
from .config import ModelConfig


def adtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_defs(cfg: ModelConfig) -> dict:
    return {"scale": ParamInfo((cfg.d_model,), cfg.param_dtype, ("embed",),
                               init_scale=0.0)}


def rmsnorm_apply(cfg: ModelConfig, p, x):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + cfg.norm_eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: [..., S] (broadcastable)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,S,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kv_in = cfg.cross_attn_dim if cross else d
    defs = {
        "wq": ParamInfo((d, h, hd), cfg.param_dtype, (None, "heads", None),
                        fsdp_dim=0),
        "wk": ParamInfo((kv_in, kv, hd), cfg.param_dtype,
                        (None, "kv_heads", None), fsdp_dim=0),
        "wv": ParamInfo((kv_in, kv, hd), cfg.param_dtype,
                        (None, "kv_heads", None), fsdp_dim=0),
        "wo": ParamInfo((h, hd, d), cfg.param_dtype, ("heads", None, None),
                        fsdp_dim=2),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamInfo((h, hd), cfg.param_dtype, ("heads", None),
                               init_scale=0.0)
        defs["bk"] = ParamInfo((kv, hd), cfg.param_dtype, ("kv_heads", None),
                               init_scale=0.0)
        defs["bv"] = ParamInfo((kv, hd), cfg.param_dtype, ("kv_heads", None),
                               init_scale=0.0)
    return defs


def _qkv(cfg: ModelConfig, p, x, kv_x):
    dt = adtype(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, mask: Optional[jax.Array]):
    """Grouped scaled-dot-product attention.

    q: [B,Sq,H,D]; k/v: [B,Skv,KV,D]; mask: broadcastable to [B,1,1,Sq,Skv].
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(b, sq, kvh, g, d)
    scale = 1.0 / np.sqrt(cfg.head_dim)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


def _sdpa_blocked(cfg: ModelConfig, q, k, v, window: int,
                  q_block: int = 512, scale: float | None = None):
    """Flash-style blocked attention (XLA-level): scan over query blocks so
    the [Sq,Skv] logits never materialize — per-block peak is
    [B,KV,G,q_block,Skv].  Causal (+ sliding window) masking is computed per
    block from positions; blocks above the diagonal are masked, not
    skipped.  Long causal self-attention takes ``_sdpa_flash`` when lowered
    for a TPU; this serves every other long path (other platforms, sliding
    windows, soft caps, MLA, meshes of more than one device)."""
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    kvh = k.shape[2]
    g = h // kvh
    scale = scale or 1.0 / np.sqrt(cfg.head_dim)
    q_block = min(q_block, sq)
    nb = sq // q_block
    assert sq % q_block == 0, (sq, q_block)
    qb = q.reshape(b, nb, q_block, h, d).transpose(1, 0, 2, 3, 4)
    # Pin layouts across the scan so XLA does not re-shard k/v (or the qb
    # slices) on every q-block iteration: without the pins, in-loop
    # all-to-alls move k and v once per query block.
    qb = shard(qb, None, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    kpos = jnp.arange(k.shape[1])

    def body(carry, inp):
        qi, blk = inp
        qi = qi.reshape(b, q_block, kvh, g, d)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qi, k).astype(
            jnp.float32) * scale
        if cfg.logit_softcap > 0:
            c = cfg.logit_softcap
            logits = c * jnp.tanh(logits / c)
        qpos = blk * q_block + jnp.arange(q_block)
        m = kpos[None, :] <= qpos[:, None]
        if window > 0:
            m = m & (qpos[:, None] - kpos[None, :] < window)
        logits = jnp.where(m[None, None, None], logits,
                           jnp.asarray(-1e30, jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
        out = out.reshape(b, q_block, h, dv)
        return carry, shard(out, "batch", None, "heads", None)

    # Inner remat: without it the scan's backward saves per-block probs —
    # i.e. the full [Sq,Skv] logits across iterations, defeating the blocked
    # structure.  With it, backward recomputes each block from q,k,v (the
    # flash-backward schedule).
    _, outs = jax.lax.scan(jax.checkpoint(body), (), (qb, jnp.arange(nb)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


# Sequences at or above this length use the blocked attention path (tests
# monkeypatch this down to cover the blocked path on CPU-sized inputs).
BLOCKED_ATTN_THRESHOLD = 2048

# Flash kernel tiles (query rows, key rows), from a sweep of 256/512/1024
# on a v5e at the train cells' shapes; a tile never exceeds the sequence.
FLASH_BLOCK_Q = 1024
FLASH_BLOCK_KV = 1024


def _sdpa_flash(cfg: ModelConfig, q, k, v):
    """Causal self-attention through the Pallas TPU flash kernel
    (kernels/flash_causal.py), forward and backward: scores and softmax
    statistics stay in VMEM, tiles above the diagonal are skipped, and the
    backward recomputes the probabilities from q, k and the saved
    log-sum-exp.  Numerics as in ``_sdpa_blocked``: softmax in f32.
    q: [B,S,H,D]; k/v: [B,S,KV,D] -> [B,S,H,D]."""
    def heads_major(x):
        return jnp.swapaxes(x, 1, 2)
    out = flash_causal.causal_flash_attention(
        heads_major(q), heads_major(k), heads_major(v),
        scale=1.0 / np.sqrt(cfg.head_dim), block_q=FLASH_BLOCK_Q,
        block_k=FLASH_BLOCK_KV)
    return heads_major(out)


def _flash_engages(cfg: ModelConfig, q, window: int) -> bool:
    """Long self-attention the kernel covers: causal with no window and no
    soft cap, shapes its tiles fit, and one device (the kernel is not
    partitioned across a mesh)."""
    mesh = current_mesh()
    return (window == 0 and cfg.logit_softcap == 0
            and (mesh is None or mesh.size == 1)
            and flash_causal.fits(q.shape[1], q.shape[-1], FLASH_BLOCK_Q,
                                  FLASH_BLOCK_KV))


# Trace-time tally of the path each traced attention layer took: "kernel"
# (``_sdpa_flash`` on a TPU lowering, ``_sdpa_blocked`` on any other),
# "blocked" or "dense".  A layer scanned n times counts n (``layer_stack``).
ATTN_PATHS: collections.Counter = collections.Counter()
_STACK_DEPTH = [1]


@contextlib.contextmanager
def layer_stack(n: int):
    """While tracing the body of a stack of ``n`` scanned layers, each
    traced attention call counts ``n`` in ``ATTN_PATHS``."""
    _STACK_DEPTH.append(_STACK_DEPTH[-1] * n)
    try:
        yield
    finally:
        _STACK_DEPTH.pop()


def _tally(path: str) -> None:
    ATTN_PATHS[path] += _STACK_DEPTH[-1]


def causal_mask(sq: int, skv: int, window: int = 0) -> jax.Array:
    """[1,1,1,Sq,Skv] boolean mask; window>0 => sliding window."""
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    m = kpos <= qpos
    if window > 0:
        m = m & (qpos - kpos < window)
    return m[None, None, None]


def decode_mask(pos: jax.Array, skv: int, window: int = 0) -> jax.Array:
    """Mask for one-token decode at absolute position ``pos``.

    ``pos`` is a scalar (shared position clock) or a ``[B]`` vector
    (per-slot position clocks, continuous batching).  Returns
    ``[1,1,1,1,Skv]`` / ``[B,1,1,1,Skv]`` respectively.
    """
    if jnp.ndim(pos) == 0:
        kpos = jnp.arange(skv)[None, :]
        m = kpos <= pos
        if window > 0:
            m = m & (pos - kpos < window)
        return m[None, None, None]
    kpos = jnp.arange(skv)[None, :]
    p = pos[:, None]
    m = kpos <= p
    if window > 0:
        m = m & (p - kpos < window)
    return m[:, None, None, None, :]


def attention_apply(cfg: ModelConfig, p, x, *, positions, window: int = 0,
                    cache: Optional[dict] = None, kv_x=None):
    """Self/cross attention.

    Train (cache None): full-sequence causal (+window) attention.
    Decode (cache dict with k,v,[pos]): x is [B,1,D]; returns updated cache.
    Cross attention (kv_x set): no mask, no cache update of kv_x.
    """
    cross = kv_x is not None
    kv_src = kv_x if cross else x
    q, k, v = _qkv(cfg, p, x, kv_src)
    if not cross:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions if cache is None else positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)

    new_cache = None
    if cross:
        mask = None
    elif cache is None:
        if x.shape[1] >= BLOCKED_ATTN_THRESHOLD:
            def blocked(q, k, v):
                return _sdpa_blocked(cfg, q, k, v, window)
            if _flash_engages(cfg, q, window):
                _tally("kernel")
                out = jax.lax.platform_dependent(
                    q, k, v, tpu=functools.partial(_sdpa_flash, cfg),
                    default=blocked)
            else:
                _tally("blocked")
                out = blocked(q, k, v)
            dt_ = adtype(cfg)
            y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt_))
            return shard(y, "batch", "seq", "embed"), None
        mask = causal_mask(x.shape[1], x.shape[1], window)
    else:
        pos = cache["pos"]
        length = cache["k"].shape[1]
        per_slot = jnp.ndim(pos) > 0   # [B] position clocks (continuous
        #                                batching) vs one shared scalar
        if window > 0 and length <= window:
            # Ring buffer: slot j holds absolute position pos-((pos-j) mod L).
            slot = jnp.mod(pos, length)
            if per_slot:
                rows = jnp.arange(k.shape[0])
                k_all = cache["k"].at[rows, slot].set(k[:, 0])
                v_all = cache["v"].at[rows, slot].set(v[:, 0])
                abs_pos = pos[:, None] - jnp.mod(
                    pos[:, None] - jnp.arange(length)[None, :], length)
                mask = (abs_pos >= 0)[:, None, None, None, :]
            else:
                k_all = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k, slot, axis=1)
                v_all = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v, slot, axis=1)
                abs_pos = pos - jnp.mod(pos - jnp.arange(length), length)
                mask = (abs_pos >= 0)[None, None, None, None, :]
        else:
            if per_slot:
                rows = jnp.arange(k.shape[0])
                k_all = cache["k"].at[rows, pos].set(k[:, 0])
                v_all = cache["v"].at[rows, pos].set(v[:, 0])
            else:
                k_all = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k, pos, axis=1)
                v_all = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v, pos, axis=1)
            mask = decode_mask(pos, length, window)
        new_cache = {"k": k_all, "v": v_all, "pos": pos + 1}
        k, v = k_all, v_all

    _tally("dense")
    out = _sdpa(cfg, q, k, v, mask)
    dt = adtype(cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    y = shard(y, "batch", None, "embed")
    return (y, new_cache) if cache is not None else (y, None)


def attn_cache_defs(cfg: ModelConfig, batch: int, max_len: int,
                    window: int = 0) -> dict:
    """KV-cache ParamInfo tree for one attention layer."""
    s = min(max_len, window) if window > 0 else max_len
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": ParamInfo((batch, s, kv, hd), cfg.dtype,
                       ("batch", "kv_seq", "kv_heads", None)),
        "v": ParamInfo((batch, s, kv, hd), cfg.dtype,
                       ("batch", "kv_seq", "kv_heads", None)),
    }


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "wi": ParamInfo((d, f), cfg.param_dtype, (None, "mlp"), fsdp_dim=0),
        "wg": ParamInfo((d, f), cfg.param_dtype, (None, "mlp"), fsdp_dim=0),
        "wo": ParamInfo((f, d), cfg.param_dtype, ("mlp", None), fsdp_dim=1),
    }


def mlp_apply(cfg: ModelConfig, p, x):
    dt = adtype(cfg)
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(dt))
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(dt))
    h = act(g) * h
    h = shard(h, "batch", None, "mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dt))
    return shard(y, "batch", None, "embed")


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> dict:
    defs = {"tokens": ParamInfo((cfg.vocab, cfg.d_model), cfg.param_dtype,
                                ("vocab", None), fsdp_dim=1,
                                init_scale=1.0)}
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamInfo((cfg.d_model, cfg.vocab),
                                    cfg.param_dtype, (None, "vocab"),
                                    fsdp_dim=0)
    return defs


def embed_apply(cfg: ModelConfig, p, tokens):
    dt = adtype(cfg)
    x = jnp.take(p["tokens"].astype(dt), tokens, axis=0)
    return shard(x, "batch", None, "embed")


def unembed_apply(cfg: ModelConfig, p, x):
    dt = adtype(cfg)
    w = (p["tokens"].astype(dt).T if cfg.tie_embeddings
         else p["unembed"].astype(dt))
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return shard(logits, "batch", None, "vocab")
