"""Analytic operation counts, from shapes alone.

Model FLOPs count each multiply-add as 2 operations and include only the
matrix products the algorithm needs: norms, softmax, rotary embedding and
elementwise activations are left out, and so is any recompute.  No count
comes from ``compiled.cost_analysis()``, which counts a scanned layer body
once.
"""
from __future__ import annotations


def lm_matmul_params(conf: dict) -> int:
    """Weights that take part in a matrix product per token: every layer's
    projections and the output head (the embedding lookup is a gather)."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    hd = conf.get("head_dim", d // h)
    f = conf["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return conf["num_hidden_layers"] * per_layer + conf["vocab_size"] * d


def lm_train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward and backward FLOPs per token of a causal decoder LM trained
    on sequences of ``seq`` tokens.

    Weights: 2 FLOPs per weight forward, 4 backward, so 6 per weight.
    Attention: under the causal mask a query at position i (0-based) meets
    i + 1 keys, (seq + 1) / 2 on average; the score and value products take
    2 * h * hd FLOPs per key each forward, so 2 * h * hd * (seq + 1) per
    token forward and three times that forward and backward.
    """
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    hd = conf.get("head_dim", d // h)
    attn = 6 * h * hd * (seq + 1) * conf["num_hidden_layers"]
    return 6 * lm_matmul_params(conf) + attn


def ffn_stack_flops(blocks: int, d_model: int, d_ff: int,
                    tokens: int) -> float:
    """Forward and backward FLOPs of the gated-FFN stack (``kinds.eager``).

    Per block, forward: ``x@wi``, ``x@wg``, ``a@wo``; backward: ``a.T@dx``,
    ``dx@wo.T``, ``x.T@dh``, ``x.T@dg``, ``dh@wi.T``, ``dg@wg.T``.  Nine
    products of ``tokens x d_model x d_ff``, 2 FLOPs per multiply-add.
    """
    return 18.0 * tokens * d_model * d_ff * blocks
