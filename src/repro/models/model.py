"""Model assembly: scanned layer stacks, train forward + loss, decode step.

Layer stacks are ``lax.scan`` over stacked per-layer params — HLO size and
compile time are O(1) in depth (61-layer deepseek compiles like 1 layer).
Heterogeneous stacks (gemma3 5:1 local:global, recurrentgemma (rec,rec,attn),
llama-vision (4 self + 1 cross)) scan over *groups*: each scan step applies
the config's ``pattern`` of block kinds; remainder layers live in a scanned
``tail`` stack; deepseek's leading dense-FFN layers in a ``dense`` stack.

The paper's technique enters here: every block tags its intermediates with
``checkpoint_name`` and the scan body is wrapped in ``jax.checkpoint`` whose
policy comes from the DTR planner (cfg.remat = none|full|dtr).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..distributed.sharding import ParamInfo, shard, shape_structs
from .config import ModelConfig
from . import layers as L
from . import mla as MLA
from . import moe as MOE
from . import rglru as RG
from . import rwkv as RW


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def _block_defs(cfg: ModelConfig, kind: str, moe_layer: bool) -> dict:
    d = {"norm1": L.rmsnorm_defs(cfg), "norm2": L.rmsnorm_defs(cfg)}
    if kind in ("attn", "attn_local", "cross"):
        d["attn"] = MLA.mla_defs(cfg) if cfg.mla else L.attention_defs(cfg)
        if kind == "cross":
            d["norm_c"] = L.rmsnorm_defs(cfg)
            d["cross"] = L.attention_defs(cfg, cross=True)
        d["ffn"] = MOE.moe_defs(cfg) if moe_layer else L.mlp_defs(cfg)
    elif kind == "rglru":
        d["rec"] = RG.rglru_defs(cfg)
        d["ffn"] = L.mlp_defs(cfg)
    elif kind == "rwkv":
        d["mix"] = RW.rwkv_defs(cfg)
    else:
        raise ValueError(kind)
    return d


def _stack_info(info: ParamInfo, n: int) -> ParamInfo:
    return ParamInfo((n, *info.shape), info.dtype,
                     (None, *(info.axes or (None,) * len(info.shape))),
                     fsdp_dim=None if info.fsdp_dim is None
                     else info.fsdp_dim + 1,
                     init_scale=info.init_scale)


def _stack_tree(tree, n: int):
    return jax.tree.map(lambda i: _stack_info(i, n), tree,
                        is_leaf=lambda x: isinstance(x, ParamInfo))


def param_defs(cfg: ModelConfig) -> dict:
    defs: dict[str, Any] = {"embed": _embed_defs(cfg)}
    if cfg.n_dense_layers:
        dense = _block_defs(cfg, "attn", moe_layer=False)
        defs["dense"] = _stack_tree(dense, cfg.n_dense_layers)
    group = {f"slot{i}": _block_defs(cfg, kind, moe_layer=cfg.moe)
             for i, kind in enumerate(cfg.pattern)}
    defs["groups"] = _stack_tree(group, cfg.n_groups)
    if cfg.tail:
        tail = {f"slot{i}": _block_defs(cfg, kind, moe_layer=cfg.moe)
                for i, kind in enumerate(cfg.tail)}
        defs["tail"] = _stack_tree(tail, 1)
    defs["final_norm"] = L.rmsnorm_defs(cfg)
    return defs


def _embed_defs(cfg: ModelConfig) -> dict:
    if cfg.n_codebooks > 0:   # musicgen: K codebook tables + K output heads
        return {
            "tokens": ParamInfo((cfg.n_codebooks, cfg.vocab, cfg.d_model),
                                cfg.param_dtype, (None, "vocab", None),
                                fsdp_dim=2, init_scale=1.0),
            "unembed": ParamInfo((cfg.n_codebooks, cfg.d_model, cfg.vocab),
                                 cfg.param_dtype, (None, None, "vocab"),
                                 fsdp_dim=1),
        }
    return L.embed_defs(cfg)


def init_params(cfg: ModelConfig, key) -> Any:
    defs = param_defs(cfg)
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamInfo))
    keys = jax.random.split(key, len(leaves))

    def one(info: ParamInfo, k):
        if info.init_scale == 0.0:
            return jnp.zeros(info.shape, jnp.dtype(info.dtype))
        fan = info.shape[-1] if len(info.shape) else 1
        scale = info.init_scale if info.init_scale != 0.02 \
            else 1.0 / np.sqrt(max(fan, 1))
        return (jax.random.normal(k, info.shape) * scale).astype(
            jnp.dtype(info.dtype))

    return jax.tree.unflatten(treedef, [one(i, k) for i, k in
                                        zip(leaves, keys)])


def param_structs(cfg: ModelConfig):
    """ShapeDtypeStruct tree (lowering without allocation)."""
    return shape_structs(param_defs(cfg))


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _ffn(cfg, p, x, moe_layer: bool):
    if moe_layer:
        return MOE.moe_apply(cfg, p, x)
    return L.mlp_apply(cfg, p, x)


def block_apply(cfg: ModelConfig, kind: str, p, x, *, positions,
                moe_layer: bool, cache: Optional[dict] = None,
                img_kv=None):
    """Pre-norm residual block; returns (x, new_cache)."""
    new_cache: dict = {}
    if kind in ("attn", "attn_local", "cross"):
        h = L.rmsnorm_apply(cfg, p["norm1"], x)
        window = cfg.window if kind == "attn_local" else 0
        attn_cache = None if cache is None else cache.get("attn")
        with jax.named_scope("attn"):
            if cfg.mla:
                a, c2 = MLA.mla_apply(cfg, p["attn"], h, positions=positions,
                                      cache=attn_cache)
            else:
                a, c2 = L.attention_apply(cfg, p["attn"], h,
                                          positions=positions, window=window,
                                          cache=attn_cache)
        if c2 is not None:
            new_cache["attn"] = c2
        x = x + checkpoint_name(a, "attn_out")
        if kind == "cross":
            hc = L.rmsnorm_apply(cfg, p["norm_c"], x)
            with jax.named_scope("attn"):
                ca, _ = L.attention_apply(cfg, p["cross"], hc,
                                          positions=positions, kv_x=img_kv)
            x = x + checkpoint_name(ca, "cross_out")
        h2 = L.rmsnorm_apply(cfg, p["norm2"], x)
        with jax.named_scope("ffn"):
            f = _ffn(cfg, p["ffn"], h2, moe_layer)
        x = x + checkpoint_name(f, "ffn_out")
    elif kind == "rglru":
        h = L.rmsnorm_apply(cfg, p["norm1"], x)
        rec_cache = None if cache is None else cache.get("rec")
        r, c2 = RG.rglru_apply(cfg, p["rec"], h, cache=rec_cache)
        if c2 is not None:
            new_cache["rec"] = c2
        x = x + checkpoint_name(r, "rec_out")
        h2 = L.rmsnorm_apply(cfg, p["norm2"], x)
        x = x + checkpoint_name(L.mlp_apply(cfg, p["ffn"], h2), "ffn_out")
    elif kind == "rwkv":
        h = L.rmsnorm_apply(cfg, p["norm1"], x)
        mix_cache = None if cache is None else cache.get("mix")
        t, c2 = RW.rwkv_time_mix(cfg, p["mix"], h, cache=mix_cache)
        x = x + checkpoint_name(t, "attn_out")
        h2 = L.rmsnorm_apply(cfg, p["norm2"], x)
        f, c3 = RW.rwkv_channel_mix(cfg, p["mix"], h2, cache=mix_cache)
        x = x + checkpoint_name(f, "ffn_out")
        if c2 is not None:
            new_cache["mix"] = {**c2, **(c3 or {})}
    else:
        raise ValueError(kind)
    x = shard(x, "batch", "seq", "embed")
    return x, (new_cache or None)


# ---------------------------------------------------------------------------
# Remat policy (the paper's technique, applied to the scan body)
# ---------------------------------------------------------------------------

def remat_policy(cfg: ModelConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    if cfg.remat == "dtr":
        # A fixed save-set: the attention and FFN block outputs, the
        # residual-stream checkpoints DTR keeps on homogeneous stacks.  No
        # planner picks it yet (core.planner.plan is not on this path).
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_out")
    if cfg.remat.startswith("names:"):
        names = [n for n in cfg.remat[6:].split(",") if n]
        return jax.checkpoint_policies.save_only_these_names(*names)
    raise ValueError(cfg.remat)


def _maybe_remat(cfg: ModelConfig, fn):
    pol = remat_policy(cfg)
    if pol is None:
        return fn
    return jax.checkpoint(fn, policy=pol)


# ---------------------------------------------------------------------------
# Forward (train)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _opt_barrier(tree):
    """Differentiable ``optimization_barrier`` (older jax has no AD rule).

    The barrier is identity; cotangents pass through their own barrier so the
    backward pass keeps the same hoisting protection as the forward.
    """
    return jax.lax.optimization_barrier(tree)


def _opt_barrier_fwd(tree):
    return jax.lax.optimization_barrier(tree), None


def _opt_barrier_bwd(_, ct):
    return (jax.lax.optimization_barrier(ct),)


_opt_barrier.defvjp(_opt_barrier_fwd, _opt_barrier_bwd)


def _embed(cfg: ModelConfig, p, tokens):
    dt = L.adtype(cfg)
    if cfg.n_codebooks > 0:
        # tokens: [B,S,K]
        tabs = p["tokens"].astype(dt)
        x = sum(jnp.take(tabs[i], tokens[..., i], axis=0)
                for i in range(cfg.n_codebooks))
    else:
        x = jnp.take(p["tokens"].astype(dt), tokens, axis=0)
    if cfg.name.startswith(("gemma", "recurrentgemma")):
        x = x * np.sqrt(cfg.d_model).astype(dt)
    return shard(x, "batch", "seq", "embed")


def _unembed(cfg: ModelConfig, p, x):
    dt = L.adtype(cfg)
    if cfg.n_codebooks > 0:
        logits = jnp.einsum("bsd,kdv->bskv", x, p["unembed"].astype(dt))
    elif cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["tokens"].astype(dt))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"].astype(dt))
    return shard(logits, "batch", None, "vocab")


def forward(cfg: ModelConfig, params, tokens, img_embed=None):
    """Full-sequence forward -> logits.

    tokens: [B,S] int32 (or [B,S,K] for codebook models).
    img_embed: [B,N,cross_dim] for VLM backbones (stub frontend output).
    """
    x = _embed(cfg, params["embed"], tokens)
    s = x.shape[1]
    positions = jnp.arange(s)
    img_kv = img_embed.astype(L.adtype(cfg)) if img_embed is not None else None

    def group_body(kinds, moe_on):
        def body(carry, slot_params):
            # Barrier: keep the per-layer FSDP all-gather INSIDE the scan
            # body — without it XLA commutes gather/slice and hoists the
            # full gathered param stack out of the loop (81 GiB resident
            # for deepseek-v3).
            slot_params = _opt_barrier(slot_params)
            h = carry
            for i, kind in enumerate(kinds):
                h, _ = block_apply(cfg, kind, slot_params[f"slot{i}"], h,
                                   positions=positions, moe_layer=moe_on,
                                   img_kv=img_kv)
            return h, None
        return body

    if cfg.n_dense_layers:
        def dense_body(carry, lp):
            lp = _opt_barrier(lp)
            h, _ = block_apply(cfg, "attn", lp, carry, positions=positions,
                               moe_layer=False)
            return h, None
        with L.layer_stack(cfg.n_dense_layers):
            x, _ = jax.lax.scan(_maybe_remat(cfg, dense_body), x,
                                params["dense"])

    body = _maybe_remat(cfg, group_body(cfg.pattern, cfg.moe))
    with L.layer_stack(cfg.n_groups):
        x, _ = jax.lax.scan(body, x, params["groups"])

    if cfg.tail:
        tbody = _maybe_remat(cfg, group_body(cfg.tail, cfg.moe))
        x, _ = jax.lax.scan(tbody, x, params["tail"])

    with jax.named_scope("lm_head"):
        x = L.rmsnorm_apply(cfg, params["final_norm"], x)
        return _unembed(cfg, params["embed"], x)


def loss_fn(cfg: ModelConfig, params, batch):
    """Next-token cross entropy (fp32 logits for the softmax)."""
    tokens = batch["tokens"]
    logits = forward(cfg, params, tokens, batch.get("img_embed"))
    with jax.named_scope("lm_head"):
        logits = logits.astype(jnp.float32)
        inp, tgt = logits[:, :-1], tokens[:, 1:]
        logp = jax.nn.log_softmax(inp, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        if cfg.n_codebooks == 0:
            nll = nll[..., 0]
        return jnp.mean(nll)


# ---------------------------------------------------------------------------
# Decode (serve)
# ---------------------------------------------------------------------------

def _block_cache_defs(cfg: ModelConfig, kind: str, batch: int,
                      max_len: int) -> dict:
    if kind in ("attn", "attn_local", "cross"):
        window = cfg.window if kind == "attn_local" else 0
        if cfg.mla:
            return {"attn": MLA.mla_cache_defs(cfg, batch, max_len)}
        return {"attn": L.attn_cache_defs(cfg, batch, max_len, window)}
    if kind == "rglru":
        return {"rec": RG.rglru_cache_defs(cfg, batch)}
    if kind == "rwkv":
        return {"mix": RW.rwkv_cache_defs(cfg, batch)}
    raise ValueError(kind)


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    defs: dict[str, Any] = {}
    if cfg.n_dense_layers:
        defs["dense"] = _stack_tree(
            _block_cache_defs(cfg, "attn", batch, max_len),
            cfg.n_dense_layers)
    group = {f"slot{i}": _block_cache_defs(cfg, kind, batch, max_len)
             for i, kind in enumerate(cfg.pattern)}
    defs["groups"] = _stack_tree(group, cfg.n_groups)
    if cfg.tail:
        tail = {f"slot{i}": _block_cache_defs(cfg, kind, batch, max_len)
                for i, kind in enumerate(cfg.tail)}
        defs["tail"] = _stack_tree(tail, 1)
    return defs


def cache_structs(cfg: ModelConfig, batch: int, max_len: int):
    return shape_structs(cache_defs(cfg, batch, max_len))


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree.map(
        lambda i: jnp.zeros(i.shape, jnp.dtype(i.dtype)),
        cache_defs(cfg, batch, max_len),
        is_leaf=lambda x: isinstance(x, ParamInfo))


def decode_step(cfg: ModelConfig, params, token, cache, pos, img_embed=None):
    """One-token decode: token [B,1] (or [B,1,K]) at absolute position pos.

    Returns (logits, new_cache).  ``pos`` is a traced int32 scalar (one
    shared position clock) or a ``[B]`` vector of per-slot clocks —
    continuous batching, where each slot's request sits at its own
    position.  Caches are stacked per scan group and updated functionally.
    """
    x = _embed(cfg, params["embed"], token)
    # Rope wants positions broadcastable to [..., S] with S=1 here:
    # scalar -> [1]; per-slot [B] -> [B, 1].
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    img_kv = img_embed.astype(L.adtype(cfg)) if img_embed is not None else None

    def inject(c):
        return {**c, "pos": pos} if "k" in c or "ckv" in c else c

    def group_scan(kinds, pstack, cstack, moe_on):
        def body(carry, inp):
            h = carry
            slot_params, slot_cache = inp
            new_slots = {}
            for i, kind in enumerate(kinds):
                blk_cache = {k2: inject(v2) if isinstance(v2, dict) else v2
                             for k2, v2 in slot_cache[f"slot{i}"].items()}
                h, nc = block_apply(cfg, kind, slot_params[f"slot{i}"], h,
                                    positions=positions, moe_layer=moe_on,
                                    cache=blk_cache, img_kv=img_kv)
                nc = nc or {}
                # Drop the scalar 'pos' from carried cache state.
                nc = {k2: ({kk: vv for kk, vv in v2.items() if kk != "pos"}
                           if isinstance(v2, dict) else v2)
                      for k2, v2 in nc.items()}
                new_slots[f"slot{i}"] = nc
            return h, new_slots
        return body

    new_cache: dict[str, Any] = {}
    if cfg.n_dense_layers:
        def dense_body(carry, inp):
            lp, lc = inp
            blk_cache = {k2: inject(v2) for k2, v2 in lc.items()}
            h, nc = block_apply(cfg, "attn", lp, carry, positions=positions,
                                moe_layer=False, cache=blk_cache)
            nc = {k2: {kk: vv for kk, vv in v2.items() if kk != "pos"}
                  for k2, v2 in (nc or {}).items()}
            return h, nc
        with L.layer_stack(cfg.n_dense_layers):
            x, new_cache["dense"] = jax.lax.scan(
                dense_body, x, (params["dense"], cache["dense"]))

    body = group_scan(cfg.pattern, params["groups"], cache["groups"], cfg.moe)
    with L.layer_stack(cfg.n_groups):
        x, new_cache["groups"] = jax.lax.scan(
            body, x, (params["groups"], cache["groups"]))

    if cfg.tail:
        tbody = group_scan(cfg.tail, params["tail"], cache["tail"], cfg.moe)
        x, new_cache["tail"] = jax.lax.scan(
            tbody, x, (params["tail"], cache["tail"]))

    with jax.named_scope("lm_head"):
        x = L.rmsnorm_apply(cfg, params["final_norm"], x)
        logits = _unembed(cfg, params["embed"], x)
    return logits, new_cache
