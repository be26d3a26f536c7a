"""Per-kernel allclose vs pure-jnp oracle, swept over shapes & dtypes.

All Pallas kernels run in interpret mode on CPU (the TPU is the target, not
the runtime — the kernel bodies execute in Python for validation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis is optional: property tests skip, rest run
    from _hypothesis_stub import given, settings, st  # noqa: F401

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_grouped_gemm
from repro.kernels.rwkv6_chunk import rwkv6_chunk

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal,window",
    [
        (1, 2, 2, 128, 128, 64, True, 0),      # MHA causal
        (2, 4, 2, 128, 128, 64, True, 0),      # GQA
        (1, 8, 1, 256, 256, 64, True, 0),      # MQA
        (2, 2, 2, 128, 128, 64, False, 0),     # bidirectional
        (1, 2, 2, 256, 256, 64, True, 64),     # sliding window
        (1, 2, 2, 64, 256, 64, True, 0),       # kv longer than q (prefix)
        (1, 2, 2, 96, 96, 32, True, 0),        # non-multiple of block
        (1, 2, 2, 128, 128, 128, True, 0),     # wide head
    ])
def test_flash_vs_reference(b, hq, hkv, sq, skv, d, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (b, hq, sq, d), dtype)
    k = rand(ks[1], (b, hkv, skv, d), dtype)
    v = rand(ks[2], (b, hkv, skv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.flash_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **TOL[dtype])


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000),
       sq=st.sampled_from([64, 128, 192]),
       d=st.sampled_from([32, 64]),
       hq=st.sampled_from([1, 2, 4]))
def test_flash_property(seed, sq, d, hq):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = rand(ks[0], (1, hq, sq, d), jnp.float32)
    k = rand(ks[1], (1, hq, sq, d), jnp.float32)
    v = rand(ks[2], (1, hq, sq, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    expect = ref.flash_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-5, atol=3e-5)
    # Softmax convexity: outputs lie within [min, max] of values.
    assert float(out.max()) <= float(v.max()) + 1e-4
    assert float(out.min()) >= float(v.min()) - 1e-4


# ---------------------------------------------------------------------------
# RWKV6 chunked recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,s,d,chunk", [
    (2, 128, 32, 32),
    (1, 256, 64, 64),
    (4, 64, 16, 16),
    (1, 128, 64, 64),    # max supported chunk
])
def test_rwkv6_vs_reference(bh, s, d, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r = rand(ks[0], (bh, s, d), dtype)
    k = rand(ks[1], (bh, s, d), dtype)
    v = rand(ks[2], (bh, s, d), dtype)
    # log-decay in [-4, -0.02] (realistic RWKV6 range, exp(w0+lora) bounded)
    wl = -jnp.exp(jax.random.uniform(ks[3], (bh, s, d),
                                     minval=-4.0, maxval=1.2))
    wl = wl.astype(dtype)
    u = rand(ks[4], (bh, d), dtype) * 0.3
    out = rwkv6_chunk(r, k, v, wl, u, chunk=chunk, interpret=True)
    expect = ref.rwkv6_reference(r, k, v, wl, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=4e-2 if dtype == jnp.bfloat16 else 3e-4,
                               atol=4e-2 if dtype == jnp.bfloat16 else 3e-4)


def test_rwkv6_matches_model_layer():
    """Kernel agrees with the model's own chunked formulation."""
    from repro.models.rwkv import _chunked_wkv
    b, s, h, d = 2, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    lw = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, d), minval=-3,
                                     maxval=1))
    u = jax.random.normal(ks[4], (h, d)) * 0.3
    model_out = _chunked_wkv(r, k, v, lw, u)
    kern = rwkv6_chunk(
        r.swapaxes(1, 2).reshape(b * h, s, d),
        k.swapaxes(1, 2).reshape(b * h, s, d),
        v.swapaxes(1, 2).reshape(b * h, s, d),
        lw.swapaxes(1, 2).reshape(b * h, s, d),
        jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, d),
        chunk=16, interpret=True)
    kern = kern.reshape(b, h, s, d).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(model_out),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MoE grouped GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,d,f,bc,bf,bd", [
    (4, 128, 256, 128, 64, 64, 128),
    (8, 64, 128, 256, 64, 128, 64),
    (2, 256, 512, 64, 128, 64, 256),
    (1, 128, 128, 128, 128, 128, 128),
])
def test_moe_gemm_vs_reference(e, c, d, f, bc, bf, bd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = rand(ks[0], (e, c, d), dtype)
    w = rand(ks[1], (e, d, f), dtype)
    out = moe_grouped_gemm(x, w, block_c=bc, block_f=bf, block_d=bd,
                           interpret=True)
    expect = ref.moe_gemm_reference(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ops_adapters():
    from repro.kernels import ops
    b, s, h, kv, d = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    a1 = ops.attention(q, k, v, use_kernel=True, interpret=True)
    a2 = ops.attention(q, k, v, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               rtol=3e-5, atol=3e-5)


def test_rwkv6_rejects_overlong_chunk():
    """Chunks beyond the separable-decay overflow bound must be rejected."""
    r = jnp.ones((1, 128, 16))
    with pytest.raises(AssertionError, match="overflows"):
        rwkv6_chunk(r, r, r, -r, jnp.ones((1, 16)), chunk=128,
                    interpret=True)


# ---------------------------------------------------------------------------
# Compiled (non-interpret) lowering — accelerator-only
# ---------------------------------------------------------------------------

@pytest.mark.requires_accel
@pytest.mark.parametrize("kernel", ["flash", "rwkv6", "moe"])
def test_kernels_compiled_on_accelerator(kernel):
    """Mosaic-compiled kernels must match the same references as interpret.

    Skipped on CPU-only hosts (conftest ``requires_accel``); interpret-mode
    equivalence above covers the kernel bodies everywhere.  Kernel and
    reference run with float32 matmuls, as interpret mode on the CPU does:
    at a TPU's default precision float32 operands are multiplied in bf16.
    Blocks keep to the TPU's (8, 128) tiling rule.
    """
    with jax.default_matmul_precision("highest"):
        _compiled_kernel_matches_reference(kernel)


def _compiled_kernel_matches_reference(kernel):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    if kernel == "flash":
        q = rand(ks[0], (1, 2, 128, 64), jnp.float32)
        k = rand(ks[1], (1, 2, 128, 64), jnp.float32)
        v = rand(ks[2], (1, 2, 128, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=False)
        expect = ref.flash_reference(q, k, v, causal=True)
    elif kernel == "rwkv6":
        r = rand(ks[0], (2, 128, 32), jnp.float32)
        k = rand(ks[1], (2, 128, 32), jnp.float32)
        v = rand(ks[2], (2, 128, 32), jnp.float32)
        wl = -jnp.exp(jax.random.uniform(ks[3], (2, 128, 32),
                                         minval=-4.0, maxval=1.2))
        u = rand(ks[4], (2, 32), jnp.float32) * 0.3
        out = rwkv6_chunk(r, k, v, wl, u, chunk=32, interpret=False)
        expect = ref.rwkv6_reference(r, k, v, wl, u)
    else:
        x = rand(ks[0], (4, 128, 256), jnp.float32)
        w = rand(ks[1], (4, 256, 128), jnp.float32) * 0.05
        out = moe_grouped_gemm(x, w, block_c=64, block_f=128, block_d=128,
                               interpret=False)
        expect = ref.moe_gemm_reference(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-4, atol=2e-4)
