"""Mixture-of-Experts with sort-based capacity dispatch (expert-parallel).

TPU-native adaptation of MegaBlocks-style dispatch: per batch row, token→
expert assignments are sorted by expert id, packed into fixed-capacity expert
buffers (equal blocks => MXU-friendly grouped einsum, no ragged ops), experts
computed as a block-diagonal einsum with the expert dim sharded over the
``model`` mesh axis (EP), and results scattered back with combine weights.
Dropped tokens (overflow beyond capacity) pass through the residual, standard
for capacity-based routing.

Covers mixtral-8x7b (8e top-2, softmax gate, renorm) and deepseek-v3 (256e
top-8 + 1 shared expert, sigmoid gate with renorm — per the paper).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import ParamInfo, shard
from .config import ModelConfig
from .layers import adtype, mlp_apply, mlp_defs


def moe_defs(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    defs = {
        "router": ParamInfo((d, e), "float32", (None, "expert")),
        # FSDP dim: sharding the non-contracting dim instead was tried and
        # REFUTED — XLA materializes the fully-gathered expert stack
        # (333 GiB/device); the contracting-dim layout costs partial-sum
        # all-reduces but stays 7x smaller.
        "wi": ParamInfo((e, d, f), cfg.param_dtype,
                        ("expert", None, "mlp"), fsdp_dim=1),
        "wg": ParamInfo((e, d, f), cfg.param_dtype,
                        ("expert", None, "mlp"), fsdp_dim=1),
        "wo": ParamInfo((e, f, d), cfg.param_dtype,
                        ("expert", "mlp", None), fsdp_dim=2),
    }
    if cfg.n_shared_experts > 0:
        defs["shared"] = mlp_defs(
            cfg, d_ff=cfg.n_shared_experts * (cfg.moe_d_ff or cfg.d_ff))
    return defs


def expert_capacity(cfg: ModelConfig, tokens_per_row: int) -> int:
    c = int(np.ceil(tokens_per_row * cfg.top_k / cfg.n_experts
                    * cfg.capacity_factor))
    return max(8, int(np.ceil(c / 8)) * 8)


def _dispatch_row(e_flat: jax.Array, capacity: int, n_experts: int):
    """Per-row dispatch indices.

    e_flat: [S*k] expert id per assignment (row-major over (token, k)).
    Returns (src_assign, slot, keep): for each sorted assignment, its source
    assignment index, its slot in the [E*C] buffer, and validity.
    """
    order = jnp.argsort(e_flat)                      # stable
    se = e_flat[order]
    group_start = jnp.searchsorted(se, jnp.arange(n_experts))
    pos = jnp.arange(se.shape[0]) - group_start[se]
    keep = pos < capacity
    slot = se * capacity + jnp.minimum(pos, capacity - 1)
    return order, slot, keep


def moe_apply(cfg: ModelConfig, p, x):
    """x: [B, S, d] -> [B, S, d]."""
    dt = adtype(cfg)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = expert_capacity(cfg, s)

    # Router (fp32 for stable softmax/sigmoid).
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if cfg.n_shared_experts > 0:   # deepseek-style sigmoid scoring
        scores = jax.nn.sigmoid(logits)
    else:                          # mixtral-style softmax scoring
        scores = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(scores, k)            # [B,S,k]
    topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-9)
    topw = topw.astype(dt)

    e_flat = topi.reshape(b, s * k)
    w_flat = topw.reshape(b, s * k)

    order, slot, keep = jax.vmap(
        lambda ef: _dispatch_row(ef, cap, e))(e_flat)
    src_tok = order // k                              # token index per slot

    # Gather tokens into expert buffers [B, E*C, d].  All scatters/gathers
    # are vmapped over batch so the batch dim is a *scatter batch dim* —
    # 2D-indexed .at[bidx, slot] forms are unpartitionable and force XLA
    # SPMD to replicate the full dispatch buffer (30 GB/layer for
    # deepseek-v3).
    gathered = jax.vmap(lambda xr, tr: xr[tr])(x, src_tok)
    gathered = gathered * keep[..., None].astype(dt)
    buf = jax.vmap(
        lambda g, sl: jnp.zeros((e * cap, d), dtype=dt).at[sl].set(g))(
        gathered, slot)
    buf = buf.reshape(b, e, cap, d)
    buf = shard(buf, "batch", "expert", None, None)

    # Grouped expert FFN (block-diagonal einsum; E sharded over model).
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    h = jnp.einsum("becd,edf->becf", buf, p["wi"].astype(dt))
    g = jnp.einsum("becd,edf->becf", buf, p["wg"].astype(dt))
    h = act(g) * h
    y = jnp.einsum("becf,efd->becd", h, p["wo"].astype(dt))
    y = shard(y, "batch", "expert", None, None)
    y = y.reshape(b, e * cap, d)

    # Scatter back with combine weights (vmapped: see note above).
    w_sorted = jnp.take_along_axis(w_flat, order, axis=1)
    contrib = jax.vmap(lambda yr, sl: yr[sl])(y, slot)
    contrib = contrib * (w_sorted * keep)[..., None].astype(dt)
    out = jax.vmap(
        lambda c, tk: jnp.zeros((s, d), dtype=dt).at[tk].add(c))(
        contrib, src_tok)
    out = shard(out, "batch", None, "embed")

    if cfg.n_shared_experts > 0:
        out = out + mlp_apply(cfg, p["shared"], x)
    return out


def aux_load_balance_loss(cfg: ModelConfig, x, p) -> jax.Array:
    """Switch-style load-balance auxiliary loss (fraction * probability)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, topi = jax.lax.top_k(probs, cfg.top_k)
    onehot = jax.nn.one_hot(topi, cfg.n_experts, dtype=jnp.float32)
    frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))
    prob = jnp.mean(probs, axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac * prob)
