"""RWKV6 ("Finch") — attention-free time-mix with data-dependent decay.

TPU-native adaptation: the GPU reference uses a custom CUDA scan; here the
recurrence is computed in *chunked* form — within-chunk interactions as an
MXU-friendly masked matmul, cross-chunk state carried by ``lax.scan`` — the
same reformulation used for linear attention on TPU.  Decode is a single
state-update step.

Faithful pieces: per-channel data-dependent decay w_t = exp(−exp(w0 + LoRA(x)))
(Finch's core novelty), bonus ``u`` term, token-shift mixing, silu output
gate, grouped head norm, squared-ReLU channel-mix.  One simplification:
token-shift mixing coefficients are learned-static (μ) rather than the
paper's data-dependent ddlerp — the recurrence itself keeps full data
dependence.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..distributed.sharding import ParamInfo, shard
from .config import ModelConfig
from .layers import adtype

_LORA = 64
_CHUNK = 16


def rwkv_defs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    h = d // cfg.rwkv_head_dim
    dh = cfg.rwkv_head_dim
    pd = cfg.param_dtype
    return {
        # time-mix
        "mu": ParamInfo((5, d), pd, (None, None), init_scale=0.5),
        "w0": ParamInfo((d,), pd, (None,), init_scale=-0.6),
        "wA": ParamInfo((d, _LORA), pd, (None, None)),
        "wB": ParamInfo((_LORA, d), pd, (None, None)),
        "u": ParamInfo((h, dh), pd, ("heads", None), init_scale=0.3),
        "wr": ParamInfo((d, d), pd, (None, "heads"), fsdp_dim=0),
        "wk": ParamInfo((d, d), pd, (None, "heads"), fsdp_dim=0),
        "wv": ParamInfo((d, d), pd, (None, "heads"), fsdp_dim=0),
        "wg": ParamInfo((d, d), pd, (None, "heads"), fsdp_dim=0),
        "wout": ParamInfo((d, d), pd, ("heads", None), fsdp_dim=1),
        "ln_x": ParamInfo((d,), pd, (None,), init_scale=0.0),
        # channel-mix
        "mu_c": ParamInfo((2, d), pd, (None, None), init_scale=0.5),
        "wr_c": ParamInfo((d, d), pd, (None, None), fsdp_dim=0),
        "wk_c": ParamInfo((d, f), pd, (None, "mlp"), fsdp_dim=0),
        "wv_c": ParamInfo((f, d), pd, ("mlp", None), fsdp_dim=1),
    }


def rwkv_cache_defs(cfg: ModelConfig, batch: int) -> dict:
    d = cfg.d_model
    h, dh = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    return {
        "state": ParamInfo((batch, h, dh, dh), "float32",
                           ("batch", "heads", None, None)),
        "x_att": ParamInfo((batch, d), cfg.dtype, ("batch", None)),
        "x_ffn": ParamInfo((batch, d), cfg.dtype, ("batch", None)),
    }


def _shift(x, prev=None):
    """x_{t-1} along seq; ``prev`` fills t=0 (decode carries it)."""
    if prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return jnp.concatenate([prev[:, None, :], x[:, :-1]], axis=1)


def _mix(x, xs, mu):
    return x + (xs - x) * mu


def _chunked_wkv(r, k, v, logw, u):
    """Chunked linear-attention recurrence with per-channel decay.

    r,k,v: [B,S,H,D]; logw: [B,S,H,D] (log decay, <=0); u: [H,D].
    Returns out [B,S,H,D].
    """
    b, s, h, d = r.shape
    c = _CHUNK
    assert s % c == 0, f"seq {s} not divisible by chunk {c}"
    n = s // c
    rc = r.reshape(b, n, c, h, d).transpose(1, 0, 3, 2, 4)   # [N,B,H,C,D]
    kc = k.reshape(b, n, c, h, d).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, n, c, h, d).transpose(1, 0, 3, 2, 4)
    lwc = logw.reshape(b, n, c, h, d).transpose(1, 0, 3, 2, 4).astype(
        jnp.float32)

    tri_lower = jnp.tril(jnp.ones((c, c), bool), k=-1)        # τ < t

    def body(state, inp):
        rcu, kcu, vcu, lw = inp                               # [B,H,C,D]
        rcu = rcu.astype(jnp.float32)
        kcu = kcu.astype(jnp.float32)
        vcu = vcu.astype(jnp.float32)
        cum = jnp.cumsum(lw, axis=2)                          # logW_t
        cum_prev = cum - lw                                   # logW_{t-1}
        # inter-chunk: (r_t * W_{t-1}) @ S0
        inter = jnp.einsum("bhtd,bhde->bhte", rcu * jnp.exp(cum_prev), state)
        # intra-chunk: A[t,τ] = Σ_d r_t k_τ exp(logW_{t-1} - logW_τ), τ<t
        diff = cum_prev[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,H,t,τ,D]
        diff = jnp.where(tri_lower[None, None, :, :, None], diff, -1e30)
        att = jnp.einsum("bhtd,bhsd,bhtsd->bhts", rcu, kcu, jnp.exp(diff))
        # diagonal bonus: r_t·(u ⊙ k_t) v_t
        diag = jnp.einsum("bhtd,hd,bhtd->bht", rcu, u.astype(jnp.float32),
                          kcu)
        intra = jnp.einsum("bhts,bhse->bhte", att, vcu) \
            + diag[..., None] * vcu
        # state update: S1 = W_C ⊙ S0 + Σ_τ (W_C/W_τ ⊙ k_τ) v_τ^T
        wtot = cum[:, :, -1:, :]                              # logW_C
        kdec = kcu * jnp.exp(wtot - cum)
        new_state = state * jnp.exp(wtot.squeeze(2))[..., None] \
            + jnp.einsum("bhsd,bhse->bhde", kdec, vcu)
        return new_state, inter + intra

    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    _, outs = jax.lax.scan(body, s0, (rc, kc, vc, lwc))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, s, h, d)
    return out


def _head_norm(cfg, p, x):
    """Per-head RMS norm with learned scale (GroupNorm analogue)."""
    b, s, h, d = x.shape
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + 1e-5)
    y = y.reshape(b, s, h * d) * (1.0 + p["ln_x"].astype(jnp.float32))
    return y


def rwkv_time_mix(cfg: ModelConfig, p, x, *, cache: Optional[dict] = None):
    dt = adtype(cfg)
    b, s, d = x.shape
    h, dh = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    xs = _shift(x, None if cache is None else cache["x_att"])
    mu = p["mu"].astype(dt)
    xr, xk, xv, xw, xg = (_mix(x, xs, mu[i]) for i in range(5))

    r = jnp.einsum("bsd,de->bse", xr, p["wr"].astype(dt)).reshape(b, s, h, dh)
    k = jnp.einsum("bsd,de->bse", xk, p["wk"].astype(dt)).reshape(b, s, h, dh)
    v = jnp.einsum("bsd,de->bse", xv, p["wv"].astype(dt)).reshape(b, s, h, dh)
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", xg, p["wg"].astype(dt)))
    # Data-dependent decay (Finch): w = exp(-exp(w0 + tanh(x A) B)).
    lora = jnp.einsum("bsl,ld->bsd",
                      jnp.tanh(jnp.einsum("bsd,dl->bsl", xw,
                                          p["wA"].astype(dt))),
                      p["wB"].astype(dt))
    logw = -jnp.exp(jnp.clip(p["w0"].astype(jnp.float32)
                             + lora.astype(jnp.float32), -8.0, 4.0))
    logw = logw.reshape(b, s, h, dh)

    if cache is None:
        out = _chunked_wkv(r, k, v, logw, p["u"])
        new_cache = None
    else:
        state = cache["state"]                                 # [B,H,D,D]
        r1 = r[:, 0].astype(jnp.float32)
        k1 = k[:, 0].astype(jnp.float32)
        v1 = v[:, 0].astype(jnp.float32)
        w1 = jnp.exp(logw[:, 0])
        u = p["u"].astype(jnp.float32)
        kv = jnp.einsum("bhd,bhe->bhde", k1, v1)
        out = jnp.einsum("bhd,bhde->bhe", r1, state + u[None, :, :, None] * kv)
        out = out[:, None].reshape(b, 1, h, dh)
        state = state * w1[..., None] + kv
        new_cache = {"state": state, "x_att": x[:, -1]}

    y = _head_norm(cfg, p, out).astype(dt) * g
    y = jnp.einsum("bsd,de->bse", y, p["wout"].astype(dt))
    return shard(y, "batch", None, "embed"), new_cache


def rwkv_channel_mix(cfg: ModelConfig, p, x, *,
                     cache: Optional[dict] = None):
    dt = adtype(cfg)
    xs = _shift(x, None if cache is None else cache["x_ffn"])
    mu = p["mu_c"].astype(dt)
    xk, xr = _mix(x, xs, mu[0]), _mix(x, xs, mu[1])
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, p["wr_c"].astype(dt)))
    k = jnp.einsum("bsd,df->bsf", xk, p["wk_c"].astype(dt))
    k = jnp.square(jax.nn.relu(k))
    k = shard(k, "batch", None, "mlp")
    y = r * jnp.einsum("bsf,fd->bsd", k, p["wv_c"].astype(dt))
    new_cache = None if cache is None else {"x_ffn": x[:, -1]}
    return shard(y, "batch", None, "embed"), new_cache
