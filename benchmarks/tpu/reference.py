"""Plain float32 references: the decoder LM's training steps and the
gated-FFN stack's gradients.

Written from the published architectures (Llama / Qwen2: pre-norm RMSNorm,
rotary embedding on halves, grouped-query causal attention, gated SiLU
MLP, tied output head) in straightforward ``jax.numpy``, importing nothing
of the program.  Matrix products run at ``highest`` precision, since a
TPU multiplies float32 in bfloat16 by default.

To fit the chip the gradient is taken layer by layer: the forward keeps
only the residual stream, and the backward re-runs one layer at a time
under ``jax.vjp``, in blocks of rows.  That changes the order of no sum
that matters and recomputes nothing the gradient depends on.

``precision="fp8"`` is the control: the same computation with both
operands of every weight product rounded to float8 (e4m3 forward, e5m2
backward, each tensor scaled to its largest magnitude), the step below the
bfloat16 that the configurations state.
"""
from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Precision of weight products
# ---------------------------------------------------------------------------

def _round_to(x, dtype):
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _round_to(x, jnp.float8_e4m3fn), None


def _fp8_bwd(_, ct):
    return (_round_to(ct, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _operand(precision: str):
    if precision == "float32":
        return lambda x: x
    if precision == "fp8":
        return _fp8
    raise ValueError(f"unknown reference precision {precision!r}")


def _dot(precision):
    q = _operand(precision)

    def dot(spec, a, b):
        return jnp.einsum(spec, q(a), q(b),
                          precision=jax.lax.Precision.HIGHEST)
    return dot


# ---------------------------------------------------------------------------
# Decoder LM
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, theta):
    """x: [B, S, H, D]; rotate the two halves of D by position."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2 / d))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(conf, dot, x, w):
    """One pre-norm block on ``x: [B, S, d]`` with this layer's weights."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    h = _rmsnorm(x, w["norm1"]["scale"], eps)
    a = w["attn"]
    q = dot("bsd,dhk->bshk", h, a["wq"])
    k = dot("bsd,dhk->bshk", h, a["wk"])
    v = dot("bsd,dhk->bshk", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    b, s, nh, hd = q.shape
    rep = nh // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)       # query head j reads kv head j//rep
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + dot("bshk,hkd->bsd", o, a["wo"])
    h = _rmsnorm(x, w["norm2"]["scale"], eps)
    f = w["ffn"]
    g = dot("bsd,df->bsf", h, f["wg"])
    u = dot("bsd,df->bsf", h, f["wi"])
    return x + dot("bsf,fd->bsd", jax.nn.silu(g) * u, f["wo"])


def _head_nll(conf, dot, x, scale, emb, tgt, keep):
    """Summed next-token NLL of a block of positions ``x: [N, d]``."""
    h = _rmsnorm(x, scale, conf["rms_norm_eps"])
    logits = dot("nd,vd->nv", h, emb)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * keep)


def _pick(stack, i):
    return jax.tree.map(lambda t: t[i], stack)


@lru_cache(maxsize=None)
def _lm_fns(conf_json: str, precision: str):
    """Jitted embedding, layer forward, layer VJP and head, one set per
    configuration and precision, so that every reference in a process
    shares them.  The layer functions take the stacked weights and the
    layer's index."""
    conf = json.loads(conf_json)
    dot = _dot(precision)
    layer = partial(_layer, conf, dot)
    head = partial(_head_nll, conf, dot)

    def fwd(x, stack, i):
        return layer(x, _pick(stack, i))

    def bwd(x, stack, i, dy, acc):
        dx, dw = jax.vjp(layer, x, _pick(stack, i))[1](dy)
        return dx, jax.tree.map(jnp.add, acc, dw)

    return (jax.jit(lambda emb, toks: jnp.take(emb, toks, axis=0)),
            jax.jit(fwd), jax.jit(bwd),
            jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2))))


@partial(jax.jit, donate_argnums=0)
def _embed_grad(d_emb, toks, dy):
    """Add the lookup's scatter of ``dy: [B, S, d]`` at ``toks``."""
    return d_emb.at[toks.reshape(-1)].add(dy.reshape(-1, dy.shape[-1]))


@jax.jit
def _stack_grads(layer_grads):
    return jax.tree.map(lambda *g: jnp.stack(g), *layer_grads)


class LMReference:
    """Loss and gradient of the LM, and AdamW steps, in plain float32.

    ``rows`` rows of the batch go through a layer at a time and
    ``head_tokens`` positions through the head at a time; both only bound
    memory.
    """

    def __init__(self, conf: dict, precision: str = "float32", *,
                 rows: int = 2, head_tokens: int = 2048):
        self.conf, self.rows, self.head_tokens = conf, rows, head_tokens
        self._embed, self._fwd, self._bwd, self._head = _lm_fns(
            json.dumps(conf, sort_keys=True, allow_nan=False), precision)

    def loss_and_grad(self, weights: dict, tokens) -> tuple[float, dict]:
        """Mean next-token NLL over ``tokens: [B, S]`` and its gradient,
        in the layout of ``weights``."""
        tokens = np.asarray(tokens)
        b, s = tokens.shape
        stack = weights["groups"]["slot0"]
        n_layers = self.conf["num_hidden_layers"]
        emb = weights["embed"]["tokens"]
        rows = [slice(i, min(i + self.rows, b))
                for i in range(0, b, self.rows)]

        xs = [[self._embed(emb, tokens[r]) for r in rows]]
        for li in range(n_layers):
            xs.append([self._fwd(x, stack, li) for x in xs[-1]])

        # Head and loss over blocks of flattened positions; the last
        # position of each row has no target.
        n = b * s
        x_last = jnp.concatenate(xs[-1], axis=0).reshape(n, -1)
        tgt = np.concatenate([tokens[:, 1:], tokens[:, :1]], 1).reshape(n)
        keep = np.tile(np.arange(s) < s - 1, b).astype(np.float32)
        count = b * (s - 1)
        total = 0.0
        d_last, d_scale, d_emb = [], 0.0, 0.0
        for i in range(0, n, self.head_tokens):
            blk = slice(i, min(i + self.head_tokens, n))
            v, (dx, ds, de) = self._head(x_last[blk],
                                         weights["final_norm"]["scale"], emb,
                                         tgt[blk], keep[blk])
            total += float(v)
            d_last.append(dx)
            d_scale = d_scale + ds
            d_emb = d_emb + de
        del x_last
        dx = jnp.concatenate(d_last, 0).reshape(b, s, -1) / count
        del d_last
        dys = [dx[r] for r in rows]
        del dx
        zero = jax.tree.map(lambda t: jnp.zeros(t.shape[1:], t.dtype), stack)
        grads = [None] * n_layers
        for li in reversed(range(n_layers)):
            acc, nxt = zero, []
            for x, dy in zip(xs[li], dys):
                dxi, acc = self._bwd(x, stack, li, dy, acc)
                nxt.append(dxi)
            grads[li] = acc
            dys = nxt
            xs[li + 1] = None
        # The (tied) table's gradient: the head's, plus the lookup's
        # scatter-add of the first layer's input cotangents.
        d_emb = d_emb / count
        for r, dy in zip(rows, dys):
            d_emb = _embed_grad(d_emb, tokens[r], dy)
        grad = {
            "embed": {"tokens": d_emb},
            "groups": {"slot0": _stack_grads(grads)},
            "final_norm": {"scale": d_scale / count},
        }
        return total / count, grad


@partial(jax.jit, static_argnums=1, donate_argnums=0)
def clip_by_global_norm(grad, max_norm: float):
    """torch.nn.utils.clip_grad_norm_: scale by max_norm / (norm + 1e-6)
    when the global norm exceeds ``max_norm``."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grad)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda g: g * scale, grad)


def warmup_cosine_lr(opt: dict, step: int) -> float:
    """Learning rate of (1-based) step ``step``: linear warm-up over
    ``warmup`` steps to ``lr``, then a cosine to ``floor * lr`` at
    ``total``."""
    lr, warm, total = opt["lr"], opt["warmup"], opt["total"]
    if step < warm:
        return lr * step / warm
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    floor = opt["floor"]
    return floor * lr + (1 - floor) * lr * 0.5 * (1 + np.cos(np.pi * t))


@partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grad, m, v, lr, b1, b2, eps, wd, step):
    def leaf(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v
    out = jax.tree.map(leaf, params, grad, m, v)
    return tuple(jax.tree.map(lambda t: t[i], out,
                              is_leaf=lambda t: isinstance(t, tuple))
                 for i in range(3))


def adamw_step(opt: dict, step: int, params, grad, m, v):
    """Decoupled AdamW (Loshchilov & Hutter), weight decay on every
    weight, at the learning rate of (1-based) ``step``."""
    return _adamw(params, grad, m, v, warmup_cosine_lr(opt, step),
                  opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                  float(step))


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda v: jnp.linalg.norm(
        v.astype(jnp.float32).reshape(-1)), tree)


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.linalg.norm((a - b).reshape(-1)),
                        new, old)


_zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree))


def _by_path(tree) -> dict:
    return {jax.tree_util.keystr(k): float(v) for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaf_norms(tree) -> dict:
    """``{path: float32 norm}`` of every leaf."""
    return _by_path(_leaf_norms(tree))


def lm_train_readings(ref: LMReference, opt: dict, remake, batches, *,
                      fault: str | None = None) -> dict:
    """Follow the program's first ``len(batches)`` AdamW steps.

    ``remake()`` returns the initial weights (the benchmark's, from the
    seed).  Returns each step's loss, the per-leaf norms of the first step's
    clipped gradient, and the per-leaf norms of the weights' change after
    the last step.  ``fault="half_batch"`` takes every step on the first
    half of the rows (of the positions, for one row) only: a planted
    fault, read against a sound run.
    """
    params = remake()
    m, v = _zeros(params), _zeros(params)
    losses, grad_norms = [], None
    for i, tokens in enumerate(batches):
        if fault == "half_batch":      # half the rows, or of one row
            b, s = tokens.shape
            tokens = tokens[: b // 2] if b > 1 else tokens[:, : s // 2]
        loss, grad = ref.loss_and_grad(params, tokens)
        grad = clip_by_global_norm(grad, opt["clip"])
        if grad_norms is None:
            grad_norms = leaf_norms(grad)
        losses.append(loss)
        params, m, v = adamw_step(opt, i + 1, params, grad, m, v)
        del grad
    del m, v
    p0 = remake()                 # not kept: it would not fit beside m, v
    change = _by_path(_change_norms(params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


# ---------------------------------------------------------------------------
# Gated-FFN stack
# ---------------------------------------------------------------------------

def _ffn_block(dot, x, w):
    wi, wg, wo = w
    return x + dot("td,df->tf", jax.nn.silu(dot("td,df->tf", x, wg))
                   * dot("td,df->tf", x, wi), wo)


@lru_cache(maxsize=None)
def _ffn_fns(precision: str):
    block = partial(_ffn_block, _dot(precision))
    return (jax.jit(block),
            jax.jit(lambda x, w, dy: jax.vjp(block, x, w)[1](dy)))


class FFNReference:
    """Weight gradients of the residual gated-FFN stack whose loss is the
    token mean of ``|x_L|^2 / 2``, block by block in float32."""

    def __init__(self, precision: str = "float32"):
        self._fwd, self._bwd = _ffn_fns(precision)

    def grads(self, weights, x0) -> list:
        """``[(d_wi, d_wg, d_wo)] * blocks`` for float32 copies of the
        given weights and input."""
        ws = [tuple(t.astype(jnp.float32) for t in w) for w in weights]
        xs = [x0.astype(jnp.float32)]
        for w in ws:
            xs.append(self._fwd(xs[-1], w))
        dy = xs.pop() / x0.shape[0]
        out = []
        for w in reversed(ws):
            dy, dw = self._bwd(xs.pop(), w, dy)
            out.append(dw)
        return out[::-1]
