"""Edge-case model tests: windowed ring-buffer wraparound, MoE capacity
semantics, RG-LRU/RWKV state behaviour over long horizons."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.models import moe as MOE


def test_ring_buffer_wraps_correctly():
    """Decode far past the window: ring-buffer cache must equal a full-cache
    decode restricted to the window."""
    cfg = configs.get_smoke("mixtral_8x7b").replace(
        capacity_factor=8.0, window=8)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    b, s = 2, 40  # 5x window
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    full = M.forward(cfg, params, tokens)           # train path (windowed)
    cache = M.init_cache(cfg, b, s)                 # ring: length = window
    # Cache length for attn_local layers should be the window, not s.
    k_shapes = [x.shape for x in jax.tree.leaves(cache)
                if hasattr(x, "shape") and x.ndim == 4]
    assert all(sh[1] == cfg.window for sh in k_shapes), k_shapes
    step = jax.jit(lambda p, t, c, pos: M.decode_step(cfg, p, t, c, pos))
    outs = []
    for i in range(s):
        lg, cache = step(params, tokens[:, i:i + 1], cache, jnp.int32(i))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    rel = float(jnp.max(jnp.abs(dec - full))) / (
        float(jnp.max(jnp.abs(full))) + 1e-9)
    assert rel < 2e-2, f"ring-buffer mismatch after wrap: {rel}"


def test_decode_per_slot_positions_match_scalar_clock():
    """A [B] pos vector with equal entries must reproduce the scalar-pos
    decode bit-for-bit, and staggered per-slot clocks must match running
    each slot alone at its own position (continuous batching)."""
    cfg = configs.get_smoke("qwen2-0.5b")
    key = jax.random.PRNGKey(2)
    params = M.init_params(cfg, key)
    b, s = 3, 10
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    step = jax.jit(lambda p, t, c, pos: M.decode_step(cfg, p, t, c, pos))

    # 1. uniform vector == scalar
    c_scalar = M.init_cache(cfg, b, s)
    c_vec = M.init_cache(cfg, b, s)
    for i in range(4):
        lg_s, c_scalar = step(params, tokens[:, i:i + 1], c_scalar,
                              jnp.int32(i))
        lg_v, c_vec = step(params, tokens[:, i:i + 1], c_vec,
                           jnp.full((b,), i, jnp.int32))
        np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))

    # 2. staggered clocks: decode slots at positions (4, 2, 0) in one
    # batched call; each row must equal the same-position decode of a
    # batch whose rows all sit at that position.
    offsets = [4, 2, 0]
    caches = {off: M.init_cache(cfg, b, s) for off in set(offsets)}
    for off in set(offsets):
        for i in range(off):
            _, caches[off] = step(params, tokens[:, i:i + 1], caches[off],
                                  jnp.int32(i))
    # Build a mixed cache: row j from caches[offsets[j]].
    leaves = [jax.tree.leaves(caches[off]) for off in offsets]
    treedef = jax.tree.structure(caches[offsets[0]])
    mixed_leaves = []
    for parts in zip(*leaves):
        x = parts[0]
        if x.ndim >= 2 and x.shape[1] == b:      # group-stacked leaf
            x = jnp.stack([parts[j][:, j] for j in range(b)], axis=1)
        elif x.ndim >= 1 and x.shape[0] == b:    # flat per-slot leaf
            x = jnp.stack([parts[j][j] for j in range(b)], axis=0)
        mixed_leaves.append(x)
    mixed = jax.tree.unflatten(treedef, mixed_leaves)
    tok_mixed = jnp.stack([tokens[j, offsets[j]:offsets[j] + 1]
                           for j in range(b)], axis=0)
    lg_mixed, _ = step(params, tok_mixed, mixed,
                       jnp.asarray(offsets, jnp.int32))
    for j, off in enumerate(offsets):
        lg_ref, _ = step(params, tokens[:, off:off + 1], caches[off],
                         jnp.int32(off))
        np.testing.assert_allclose(np.asarray(lg_mixed)[j],
                                   np.asarray(lg_ref)[j],
                                   rtol=2e-5, atol=2e-5)


def test_moe_capacity_drops_are_graceful():
    """Lower capacity drops tokens (outputs differ) but never NaNs, and
    capacity >= S*k/E * big is drop-free deterministic."""
    cfg = configs.get_smoke("mixtral_8x7b")
    key = jax.random.PRNGKey(1)
    d = cfg.d_model
    p = {k: v for k, v in zip(
        ["router", "wi", "wg", "wo"],
        [jax.random.normal(key, (d, cfg.n_experts)),
         jax.random.normal(key, (cfg.n_experts, d, cfg.moe_d_ff)) * 0.05,
         jax.random.normal(key, (cfg.n_experts, d, cfg.moe_d_ff)) * 0.05,
         jax.random.normal(key, (cfg.n_experts, cfg.moe_d_ff, d)) * 0.05])}
    x = jax.random.normal(key, (2, 64, d)) * 0.3
    tight = MOE.moe_apply(cfg.replace(capacity_factor=0.5), p, x)
    loose = MOE.moe_apply(cfg.replace(capacity_factor=16.0), p, x)
    assert bool(jnp.isfinite(tight).all())
    assert bool(jnp.isfinite(loose).all())
    # Tight capacity must actually drop something.
    assert float(jnp.max(jnp.abs(tight - loose))) > 1e-6


def test_moe_combine_weights_sum_effects():
    """With capacity ample, MoE output is a convex combination of expert
    outputs: scaling all expert weights scales the output."""
    cfg = configs.get_smoke("mixtral_8x7b").replace(capacity_factor=16.0)
    key = jax.random.PRNGKey(2)
    d = cfg.d_model
    p = {"router": jax.random.normal(key, (d, cfg.n_experts)),
         "wi": jax.random.normal(key, (cfg.n_experts, d, cfg.moe_d_ff)) * .05,
         "wg": jax.random.normal(key, (cfg.n_experts, d, cfg.moe_d_ff)) * .05,
         "wo": jax.random.normal(key, (cfg.n_experts, cfg.moe_d_ff, d)) * .05}
    x = jax.random.normal(key, (1, 32, d)) * 0.3
    y1 = MOE.moe_apply(cfg, p, x)
    p2 = dict(p, wo=p["wo"] * 2.0)
    y2 = MOE.moe_apply(cfg, p2, x)
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1),
                               rtol=1e-4, atol=1e-5)


def test_recurrent_state_long_horizon_stability():
    """RG-LRU / RWKV decode for 200 steps stays finite and bounded."""
    for arch in ("recurrentgemma_2b", "rwkv6_1_6b"):
        cfg = configs.get_smoke(arch)
        key = jax.random.PRNGKey(3)
        params = M.init_params(cfg, key)
        b = 1
        cache = M.init_cache(cfg, b, 256)
        step = jax.jit(lambda p, t, c, pos: M.decode_step(cfg, p, t, c, pos))
        tok = jnp.zeros((b, 1), jnp.int32)
        mx = 0.0
        for i in range(200):
            lg, cache = step(params, tok, cache, jnp.int32(i))
            tok = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
            mx = max(mx, float(jnp.max(jnp.abs(lg))))
        assert np.isfinite(mx) and mx < 1e4, (arch, mx)


def test_param_structs_match_init_shapes():
    """ShapeDtypeStruct tree (lowering input) must exactly mirror real
    init_params shapes/dtypes for every arch."""
    key = jax.random.PRNGKey(0)
    for arch in configs.ARCHS:
        cfg = configs.get_smoke(arch)
        structs = M.param_structs(cfg)
        params = M.init_params(cfg, key)
        s_leaves = jax.tree.leaves(structs)
        p_leaves = jax.tree.leaves(params)
        assert len(s_leaves) == len(p_leaves)
        for s, p in zip(s_leaves, p_leaves):
            assert s.shape == p.shape, (arch, s.shape, p.shape)
            assert s.dtype == p.dtype, (arch, s.dtype, p.dtype)
