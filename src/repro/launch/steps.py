"""Train/serve step builders with production sharding.

``make_train_step``: value_and_grad -> clip -> optimizer, with optional
gradient accumulation (scan over microbatches, f32 accumulators) — the
standard overlap structure (each microbatch's backward overlaps the implicit
DP reduction of the previous one under the XLA latency-hiding scheduler).

``state_shardings``: NamedShardings for (params, opt_state) from the ParamInfo
tree — optimizer states inherit the param's logical axes; Adafactor's factored
moments drop the corresponding dim; ZeRO-1 additionally shards states over the
data axes via the param's fsdp_dim.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.sharding import ParamInfo, param_pspec, pspec
from ..models import model as M
from ..models.config import ModelConfig
from ..optim import OptState, apply_updates, clip_by_global_norm
from ..optim.optimizers import Optimizer


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt: Optimizer, grad_accum: int = 1,
                    max_grad_norm: float = 1.0):
    """Returns train_step(params, opt_state, batch, max_loss) ->
    (params, state, metrics).

    The update is applied on the device only when the loss and grad norm
    are finite and the loss is at most ``max_loss`` (a float32 scalar; inf
    accepts every finite step); otherwise the step returns the state it was
    given, and ``metrics["applied"]`` says which.  A caller that donates the
    state can then drop a bad step without the old buffers, which donation
    has already released."""

    def loss_of(p, b):
        return M.loss_fn(cfg, p, b)

    def train_step(params, opt_state, batch, max_loss):
        if grad_accum > 1:
            def micro(b):
                return jax.tree.map(
                    lambda x: x.reshape(grad_accum,
                                        x.shape[0] // grad_accum,
                                        *x.shape[1:]), b)

            mb = micro(batch)

            def body(acc, b):
                l, g = jax.value_and_grad(loss_of)(params, b)
                acc_l, acc_g = acc
                return (acc_l + l,
                        jax.tree.map(lambda a, x:
                                     a + x.astype(jnp.float32), acc_g, g)), \
                    None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params))
            (loss_sum, grads), _ = jax.lax.scan(body, zero, mb)
            loss = loss_sum / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        updates, new_opt_state = opt.update(grads, opt_state, params)
        new_params = apply_updates(params, updates)
        loss = loss.astype(jnp.float32)
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm) & (loss <= max_loss)
        keep = partial(jax.tree.map, lambda n, o: jnp.where(ok, n, o))
        metrics = {"loss": loss, "grad_norm": gnorm, "applied": ok}
        return (keep(new_params, params), keep(new_opt_state, opt_state),
                metrics)

    return train_step


def make_serve_step(cfg: ModelConfig):
    """One-token decode step (the lowering target for decode_* shapes)."""

    def serve_step(params, cache, token, pos, img_embed=None):
        logits, cache = M.decode_step(cfg, params, token, cache, pos,
                                      img_embed=img_embed)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return serve_step


def serve_step_structs(arch: str, *, smoke: bool = True, slots: int = 4,
                       max_len: int = 64):
    """(cfg, example_args) for tracing ``make_serve_step`` without params.

    The args are ``ShapeDtypeStruct`` trees, so the step can be lowered or
    jaxpr-captured (``repro.trace``) with zero parameter allocation.
    """
    from .. import configs
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    params = M.param_structs(cfg)
    cache = M.cache_structs(cfg, slots, max_len)
    token = jax.ShapeDtypeStruct(
        (slots, 1) if not cfg.n_codebooks else (slots, 1, cfg.n_codebooks),
        np.dtype("int32"))
    pos = jax.ShapeDtypeStruct((), np.dtype("int32"))
    return cfg, (params, cache, token, pos)


# ---------------------------------------------------------------------------
# Sharding of the full train state
# ---------------------------------------------------------------------------

def _opt_state_infos(opt_name: str, defs, zero1: bool):
    """ParamInfo tree for the optimizer's inner state."""

    def promote(info: ParamInfo) -> ParamInfo:
        # ZeRO-1: force state sharding over data via fsdp_dim.
        return ParamInfo(info.shape, "float32", info.axes,
                         fsdp_dim=info.fsdp_dim, init_scale=0.0)

    is_info = lambda x: isinstance(x, ParamInfo)  # noqa: E731
    if opt_name == "adamw":
        # Layout matches optimizers.adamw: {"m": tree, "v": tree}.
        return {"m": jax.tree.map(promote, defs, is_leaf=is_info),
                "v": jax.tree.map(promote, defs, is_leaf=is_info)}
    if opt_name == "sgdm":
        return jax.tree.map(promote, defs, is_leaf=is_info)
    if opt_name == "adafactor":
        def one(info: ParamInfo):
            if len(info.shape) >= 2:
                axes = info.axes or (None,) * len(info.shape)
                vr = ParamInfo(info.shape[:-1], "float32", axes[:-1],
                               init_scale=0.0)
                vc = ParamInfo(info.shape[:-2] + info.shape[-1:],
                               "float32", axes[:-2] + axes[-1:],
                               init_scale=0.0)
                return {"vr": vr, "vc": vc}
            return {"v": ParamInfo(info.shape, "float32", info.axes,
                                   init_scale=0.0)}
        return jax.tree.map(one, defs, is_leaf=is_info)
    raise ValueError(opt_name)


def state_shardings(cfg: ModelConfig, mesh: Mesh, opt_name: str,
                    fsdp: bool = False, zero1: bool = True):
    """(param_shardings, opt_state_shardings) NamedSharding trees."""
    defs = M.param_defs(cfg)

    def of(info: ParamInfo, force_fsdp: bool):
        return NamedSharding(
            mesh, param_pspec(info, mesh=mesh, fsdp=fsdp or force_fsdp))

    p_sh = jax.tree.map(lambda i: of(i, False), defs,
                        is_leaf=lambda x: isinstance(x, ParamInfo))
    o_infos = _opt_state_infos(opt_name, defs, zero1)
    o_sh = jax.tree.map(lambda i: of(i, zero1), o_infos,
                        is_leaf=lambda x: isinstance(x, ParamInfo))
    scalar = NamedSharding(mesh, P())
    return p_sh, OptState(step=scalar, inner=o_sh)


def opt_state_structs(cfg: ModelConfig, opt_name: str):
    """ShapeDtypeStruct tree of the optimizer state (dry-run input)."""
    defs = M.param_defs(cfg)
    infos = _opt_state_infos(opt_name, defs, zero1=True)
    structs = jax.tree.map(
        lambda i: jax.ShapeDtypeStruct(i.shape, np.dtype(i.dtype)),
        infos, is_leaf=lambda x: isinstance(x, ParamInfo))
    return OptState(step=jax.ShapeDtypeStruct((), np.dtype("int32")),
                    inner=structs)


def batch_shardings(cfg: ModelConfig, mesh: Mesh, specs: dict):
    def of(struct):
        ndim = len(struct.shape)
        axes = ["batch"] + [None] * (ndim - 1)
        return NamedSharding(mesh, pspec(*axes, mesh=mesh))
    return jax.tree.map(of, specs)


def cache_shardings(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int):
    defs = M.cache_defs(cfg, batch, max_len)
    return jax.tree.map(
        lambda i: NamedSharding(mesh, param_pspec(i, mesh=mesh, fsdp=False)),
        defs, is_leaf=lambda x: isinstance(x, ParamInfo))
