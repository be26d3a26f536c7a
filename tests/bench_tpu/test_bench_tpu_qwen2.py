"""The program's Qwen2 loss and gradients against ``reference.py`` on the
CPU, at Qwen2-0.5B's own ratios: 14 query heads over 2 KV heads, QKV bias,
rope theta 1e6, RMSNorm eps 1e-6 and a tied head, at widths cut to a CPU
(heads of 8, two layers, a 256-word vocabulary).

Every weight, the biases and the norm scales among them, is drawn from
N(0, 0.02^2): the benchmark's weights start biases and scales at 0, where a
bias added in the wrong place, or a norm scale read as ``scale`` and not
``1 + scale``, would not show in the loss.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.tpu import reference, weights
from benchmarks.tpu.kinds import train

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "tpu"
QWEN2 = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
# Published: 14 x 64 = 896 wide, ff 4864 (5.43 x d), 24 layers, vocab
# 151936.  Kept: the head counts, bias, theta, eps, tied head and ff ratio.
SMALL = {**QWEN2, "head_dim": 8, "hidden_size": 14 * 8,
         "intermediate_size": 608, "num_hidden_layers": 2,
         "vocab_size": 256}
JOB = {"remat": "dtr", "dtype": "float32", "param_dtype": "float32"}

# Program and reference both compute in float32 (``highest`` matmuls); they
# differ in the order of their sums (blocked attention, masking by -1e30 or
# -inf, the reference's layer-by-layer backward).  That moved the loss by
# at most 1.8e-7 of itself and a leaf's gradient norm by 2.3e-7 of the
# median leaf's (CPU, both paths, both seeds); the limits leave about 10x
# of room.  Dropping the bias moves the loss by 1e-3 and the bias leaves'
# gradients by all of theirs.
LOSS_TOL = 2e-6
GRAD_TOL = 2e-6


def _weights(seed: int) -> dict:
    shapes = weights.lm_shapes(SMALL)
    leaves, tree = jax.tree.flatten(shapes,
                                    is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        SMALL["initializer_range"] * jax.random.normal(k, s)
        for k, s in zip(keys, leaves)])


def _gaps(seed: int, seq: int):
    """(loss gap, {leaf: gradient-norm gap}) of program vs reference."""
    from repro.models import model as M
    cfg = train.program_config(SMALL, JOB)
    params = _weights(seed)
    tokens = np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], size=(2, seq), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        loss, grad = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, {"tokens": jnp.asarray(tokens)}))(
                params)
    ref = reference.LMReference(SMALL, "float32", rows=1, head_tokens=seq)
    ref_loss, ref_grad = ref.loss_and_grad(params, tokens)
    prog_n, ref_n = reference.leaf_norms(grad), reference.leaf_norms(
        ref_grad)
    return (abs(float(loss) - ref_loss) / abs(ref_loss),
            train.relative_gaps(prog_n, ref_n, list(ref_n)))


@pytest.fixture(params=["dense", "blocked"])
def seq(request, monkeypatch):
    """A short sequence through the dense attention path, and a longer one
    through the blocked path that the chip cell's 4096 positions take."""
    if request.param == "dense":
        return 64
    from repro.models import layers
    monkeypatch.setattr(layers, "BLOCKED_ATTN_THRESHOLD", 1024)
    return 1024


@pytest.mark.parametrize("seed", [3, 2**33 + 5])
def test_qwen2_matches_the_reference(seq, seed):
    loss_gap, grad_gaps = _gaps(seed, seq)
    assert loss_gap < LOSS_TOL
    assert max(grad_gaps.values()) < GRAD_TOL, grad_gaps
    assert {"['groups']['slot0']['attn']['bq']",
            "['groups']['slot0']['attn']['bk']",
            "['groups']['slot0']['attn']['bv']"} <= set(grad_gaps)


def test_qwen2_without_its_bias_fails_the_comparison(seq, monkeypatch):
    """The same comparison, with the program's q/k/v taken without bias."""
    from repro.models import layers
    real = layers._qkv
    monkeypatch.setattr(layers, "_qkv", lambda cfg, p, x, kv_x: real(
        cfg.replace(qkv_bias=False), p, x, kv_x))
    loss_gap, grad_gaps = _gaps(3, seq)
    assert loss_gap > LOSS_TOL or max(grad_gaps.values()) > GRAD_TOL
    assert grad_gaps["['groups']['slot0']['attn']['bq']"] > 1000 * GRAD_TOL

