"""Weights and inputs made from ``--seed``, on the device, by the benchmark.

The program and the reference both take their weights from here, so the
reference needs nothing the program made.  The tree has the layout that
``repro.models.model`` reads (layers stacked on a leading axis in
``groups/slot0``); ``kinds.train`` checks it against the program's own
parameter shapes before the first step.
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``, also one past 32 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def lm_shapes(conf: dict) -> dict:
    """Shapes of a decoder LM's weights (tied embedding), program layout."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim", d // h)
    f, v = conf["intermediate_size"], conf["vocab_size"]
    attn = {"wq": (L, d, h, hd), "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
            "wo": (L, h, hd, d)}
    if conf["qkv_bias"]:
        attn.update(bq=(L, h, hd), bk=(L, kv, hd), bv=(L, kv, hd))
    return {
        "embed": {"tokens": (v, d)},
        "groups": {"slot0": {
            "norm1": {"scale": (L, d)}, "norm2": {"scale": (L, d)},
            "attn": attn,
            "ffn": {"wi": (L, d, f), "wg": (L, d, f), "wo": (L, f, d)}}},
        "final_norm": {"scale": (d,)},
    }


def lm_weights(conf: dict, key, dtype=jnp.float32) -> dict:
    """Matrices ~ N(0, initializer_range**2); norm scales and biases 0.

    The program's RMSNorm multiplies by ``1 + scale``, so a zero scale is
    the published initial norm weight of 1.
    """
    shapes = lm_shapes(conf)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(
        s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    keys = jax.random.split(key, len(flat))
    std = conf["initializer_range"]
    leaves = []
    for path, shape, k in zip(paths, flat, keys):
        if "scale" in path or path.endswith(("['bq']", "['bk']", "['bv']")):
            leaves.append(jnp.zeros(shape, dtype))
        else:
            leaves.append((std * jax.random.normal(k, shape)).astype(dtype))
    return jax.tree.unflatten(tree, leaves)


@lru_cache(maxsize=None)
def _lm_init(conf_json: str, shardings):
    out = None if shardings is None else jax.tree.unflatten(*shardings)
    return jax.jit(partial(lm_weights, json.loads(conf_json)),
                   out_shardings=out)


def lm_init(conf: dict, out_shardings=None):
    """``lm_weights`` of ``conf`` as one jitted call, made into
    ``out_shardings`` where given; one program per configuration and
    shardings in a process."""
    key = None
    if out_shardings is not None:
        leaves, tree = jax.tree.flatten(out_shardings)
        key = (tree, tuple(leaves))
    return _lm_init(json.dumps(conf, sort_keys=True, allow_nan=False), key)


def ffn_weights(blocks: int, d_model: int, d_ff: int, key,
                dtype=jnp.bfloat16) -> list:
    """``[(wi, wg, wo)] * blocks`` of the gated-FFN stack.

    ``wi``, ``wg`` ~ N(0, 1/d_model); ``wo`` ~ N(0, 1/(d_ff * blocks**2)):
    the gated product is quadratic in x, so a residual stack stays bounded
    only if each block adds little.
    """
    ks = jax.random.split(key, 3 * blocks)
    out = []
    for i in range(blocks):
        wi = jax.random.normal(ks[3 * i], (d_model, d_ff)) / math.sqrt(
            d_model)
        wg = jax.random.normal(ks[3 * i + 1], (d_model, d_ff)) / math.sqrt(
            d_model)
        wo = jax.random.normal(ks[3 * i + 2], (d_ff, d_model)) / (
            math.sqrt(d_ff) * blocks)
        out.append(tuple(w.astype(dtype) for w in (wi, wg, wo)))
    return out


def ffn_input(tokens: int, d_model: int, key, step: int,
              dtype=jnp.bfloat16):
    """The stack's input of window step ``step``: ``[tokens, d_model]``."""
    return jax.random.normal(jax.random.fold_in(key, step),
                             (tokens, d_model)).astype(dtype)
