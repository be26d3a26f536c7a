"""The causal flash kernel of the train path (kernels/flash_causal.py, run
interpreted on the CPU) against the XLA attention paths, and the rule that
picks the path (``layers.ATTN_PATHS``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import flash_causal
from repro.launch.steps import make_train_step
from repro.models import layers as L
from repro.models import model as M
from repro.optim import adamw

BF16_TOL = 2e-2      # largest |error| over largest |value|: a few bf16 ulps


def _qkv(seed, b, s, h, kv, d=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(b, s, h, d), (b, s, kv, d), (b, s, kv, d), (b, s, h, d)]
    return [jax.random.normal(k, sh, jnp.float32).astype(jnp.bfloat16)
            for k, sh in zip(ks, shapes)]


def _out_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *vjp(do))]


def _rel(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel path's Pallas kernels run in interpret mode."""
    monkeypatch.setattr(flash_causal, "causal_flash_attention",
                        functools.partial(flash_causal.causal_flash_attention,
                                          interpret=True))


@pytest.mark.parametrize("h,kv,s,bq,bk", [
    (9, 3, 256, 128, 128),      # SmolLM's 9:3 grouping
    (9, 3, 512, 256, 128),      # more key tiles than query tiles
    (14, 2, 512, 128, 256),     # Qwen2's 14:2; tiles cross the diagonal
    (14, 2, 1024, 256, 256),
])
def test_kernel_matches_xla_paths(h, kv, s, bq, bk, interpreted,
                                  monkeypatch):
    """Output and q/k/v gradients of the kernel path, the blocked path and
    the dense path agree within bf16 rounding, and the kernel is no
    further from a float32 computation than the blocked path is."""
    monkeypatch.setattr(L, "FLASH_BLOCK_Q", bq)
    monkeypatch.setattr(L, "FLASH_BLOCK_KV", bk)
    cfg = configs.get_smoke("smollm-135m").replace(
        n_heads=h, n_kv_heads=kv, head_dim=64, dtype="bfloat16")
    q, k, v, do = _qkv(s, 1, s, h, kv)
    mask = L.causal_mask(s, s)
    kernel = _out_and_grads(lambda *a: L._sdpa_flash(cfg, *a), q, k, v, do)
    blocked = _out_and_grads(
        lambda *a: L._sdpa_blocked(cfg, *a, window=0, q_block=128),
        q, k, v, do)
    dense = _out_and_grads(lambda *a: L._sdpa(cfg, *a, mask), q, k, v, do)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    exact = _out_and_grads(lambda *a: L._sdpa(cfg, *a, mask), *f32[:3],
                           f32[3])
    for name, a, b, c, e in zip(("out", "dq", "dk", "dv"), kernel, blocked,
                                dense, exact):
        assert _rel(a, b) < BF16_TOL, (name, _rel(a, b))
        assert _rel(a, c) < BF16_TOL, (name, _rel(a, c))
        assert _rel(a, e) < 1.5 * _rel(b, e) + 1e-3, (
            name, _rel(a, e), _rel(b, e))


def _train_grads(cfg, tokens):
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return jax.jit(jax.value_and_grad(
        lambda p: M.loss_fn(cfg, p, {"tokens": tokens})))(params)


def test_train_step_takes_the_kernel_like_the_chip(interpreted, monkeypatch):
    """The whole remat'd train forward and backward with the TPU branch of
    the path taken (kernel interpreted) matches the CPU lowering."""
    monkeypatch.setattr(L, "BLOCKED_ATTN_THRESHOLD", 128)
    monkeypatch.setattr(L, "FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(L, "FLASH_BLOCK_KV", 128)
    cfg = configs.get_smoke("qwen2-0.5b").replace(
        n_heads=14, n_kv_heads=2, head_dim=64, d_model=128, dtype="bfloat16",
        remat="dtr")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0,
                                cfg.vocab)
    loss_xla, g_xla = _train_grads(cfg, tokens)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *a, tpu, default: tpu(*a))
    loss_kernel, g_kernel = _train_grads(cfg, tokens)
    np.testing.assert_allclose(float(loss_kernel), float(loss_xla),
                               rtol=1e-3)
    for path, a in jax.tree_util.tree_leaves_with_path(g_kernel):
        b = g_xla
        for key in path:
            b = b[key.key]
        a, b = np.asarray(a), np.asarray(b)
        assert _rel(a, b) < 5e-2, (jax.tree_util.keystr(path), _rel(a, b))


# ---------------------------------------------------------------------------
# Which path each layer takes
# ---------------------------------------------------------------------------

def _paths(cfg, seq, *, decode=False):
    L.ATTN_PATHS.clear()
    params = M.param_structs(cfg)
    if decode:
        cache = M.cache_structs(cfg, 2, seq)
        jax.eval_shape(lambda p, c: M.decode_step(
            cfg, p, jnp.zeros((2, 1), jnp.int32), c, jnp.int32(0)),
            params, cache)
    else:
        batch = {"tokens": jax.ShapeDtypeStruct((2, seq), jnp.int32)}
        if cfg.cross_attn_tokens:
            batch["img_embed"] = jax.ShapeDtypeStruct(
                (2, cfg.cross_attn_tokens, cfg.cross_attn_dim), jnp.float32)
        jax.eval_shape(jax.grad(lambda p, b: M.loss_fn(cfg, p, b)),
                       params, batch)
    return dict(L.ATTN_PATHS)


@pytest.fixture
def long_at_256(monkeypatch):
    monkeypatch.setattr(L, "BLOCKED_ATTN_THRESHOLD", 256)


def test_kernel_engages_on_long_causal_layers(long_at_256):
    cfg = configs.get_smoke("smollm-135m")
    assert _paths(cfg, 256) == {"kernel": cfg.n_layers}
    assert _paths(cfg, 128) == {"dense": cfg.n_layers}


def test_full_size_train_steps_take_the_kernel_in_every_layer():
    for arch, batch, seq in (("smollm-135m", 16, 2048),
                             ("qwen2-0.5b", 1, 4096)):
        cfg = configs.get(arch).replace(remat="dtr")
        opt = adamw(lr=3e-4)
        params = M.param_structs(cfg)
        L.ATTN_PATHS.clear()
        jax.jit(make_train_step(cfg, opt)).trace(
            params, jax.eval_shape(opt.init, params),
            {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)},
            jax.ShapeDtypeStruct((), jnp.float32))
        assert dict(L.ATTN_PATHS) == {"kernel": cfg.n_layers}, arch


@pytest.mark.parametrize("case", ["window", "softcap", "mesh",
                                  "untileable"])
def test_xla_keeps_what_the_kernel_does_not_cover(case, long_at_256,
                                                  monkeypatch):
    cfg = configs.get_smoke("smollm-135m")
    seq = 256
    if case == "window":          # gemma3: sliding-window and global layers
        cfg = configs.get_smoke("gemma3_1b")
        kinds = list(cfg.pattern) * cfg.n_groups + list(cfg.tail)
        assert _paths(cfg, seq) == {"blocked": kinds.count("attn_local"),
                                    "kernel": kinds.count("attn")}
        return
    if case == "softcap":
        cfg = cfg.replace(logit_softcap=30.0)
    elif case == "mesh":
        class FourDevices:
            size = 4
        monkeypatch.setattr(L, "current_mesh", lambda: FourDevices())
    else:
        seq = 384                 # not a multiple of the 256-row tiles
        monkeypatch.setattr(L, "FLASH_BLOCK_Q", 256)
    assert _paths(cfg, seq) == {"blocked": cfg.n_layers}


def test_cross_decode_and_mla_stay_on_xla(long_at_256):
    vision = configs.get_smoke("llama3_2_vision_11b")
    # Every layer's self-attention takes the kernel; the cross-attention
    # of each "cross" layer stays dense.
    assert _paths(vision, 256) == {"kernel": vision.n_layers,
                                   "dense": vision.n_groups}
    cfg = configs.get_smoke("smollm-135m")
    assert _paths(cfg, 256, decode=True) == {"dense": cfg.n_layers}
    mla = configs.get_smoke("deepseek_v3_671b")
    assert "kernel" not in _paths(mla, 256)


def test_cpu_lowering_runs_the_blocked_path(long_at_256):
    """Where the kernel engages, the CPU program holds no kernel and gives
    the blocked path's loss."""
    cfg = configs.get_smoke("smollm-135m")
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 256), 0,
                                cfg.vocab)
    step = jax.jit(lambda p: M.loss_fn(cfg, p, {"tokens": tokens}))
    hlo = step.lower(params).compile().as_text()
    assert "tpu_custom_call" not in hlo
    with_rule = float(step(params))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(L, "_flash_engages", lambda *a: False)
        blocked = float(jax.jit(
            lambda p: M.loss_fn(cfg, p, {"tokens": tokens}))(params))
    assert with_rule == blocked
