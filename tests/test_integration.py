"""Integration tests: blocked-attention paths through full models, training
convergence on the structured synthetic data, end-to-end resume."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt import CheckpointManager
from repro.data.pipeline import SyntheticLM
from repro.launch.steps import make_train_step
from repro.models import layers as L
from repro.models import model as M
from repro.optim import adamw, apply_updates, clip_by_global_norm

NO_LIMIT = np.float32(np.inf)     # train steps' max_loss: apply every update


class TestBlockedPaths:
    """The blocked (flash-style) attention paths must match the plain path
    through the FULL model, not just the kernel (covers masking, GQA
    grouping, RoPE interaction, MLA concat layout)."""

    def _loss(self, cfg, params, tokens):
        return float(jax.jit(lambda p: M.loss_fn(cfg, p,
                                                 {"tokens": tokens}))(params))

    @pytest.mark.parametrize("arch", ["llama3_2_1b", "gemma3_1b",
                                      "mixtral_8x7b"])
    def test_blocked_attention_matches_plain(self, arch, monkeypatch):
        cfg = configs.get_smoke(arch)
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        tokens = jax.random.randint(key, (2, 128), 0, cfg.vocab)
        plain = self._loss(cfg, params, tokens)
        monkeypatch.setattr(L, "BLOCKED_ATTN_THRESHOLD", 64)
        blocked = self._loss(cfg, params, tokens)
        np.testing.assert_allclose(blocked, plain, rtol=1e-5)

    def test_blocked_mla_matches_plain(self, monkeypatch):
        cfg = configs.get_smoke("deepseek_v3_671b").replace(
            capacity_factor=8.0)
        key = jax.random.PRNGKey(1)
        params = M.init_params(cfg, key)
        tokens = jax.random.randint(key, (2, 128), 0, cfg.vocab)
        plain = self._loss(cfg, params, tokens)
        monkeypatch.setattr(L, "BLOCKED_ATTN_THRESHOLD", 64)
        blocked = self._loss(cfg, params, tokens)
        np.testing.assert_allclose(blocked, plain, rtol=1e-5)

    def test_blocked_gradients_match(self, monkeypatch):
        cfg = configs.get_smoke("llama3_2_1b")
        key = jax.random.PRNGKey(2)
        params = M.init_params(cfg, key)
        tokens = jax.random.randint(key, (2, 128), 0, cfg.vocab)
        g = jax.jit(jax.grad(lambda p: M.loss_fn(cfg, p,
                                                 {"tokens": tokens})))
        g_plain = g(params)
        monkeypatch.setattr(L, "BLOCKED_ATTN_THRESHOLD", 64)
        g_block = jax.jit(jax.grad(
            lambda p: M.loss_fn(cfg, p, {"tokens": tokens})))(params)
        for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_block)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)


class TestTrainingConverges:
    @pytest.mark.parametrize("arch", ["smollm_135m", "rwkv6_1_6b"])
    def test_loss_decreases(self, arch):
        """The structured synthetic stream (bigram permutation) is
        learnable; 40 steps must visibly reduce loss."""
        cfg = configs.get_smoke(arch).replace(vocab=128, dtype="float32")
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        opt = adamw(lr=3e-3)
        state = opt.init(params)
        step = jax.jit(make_train_step(cfg, opt))
        data = SyntheticLM(vocab=cfg.vocab, seq_len=32, batch=8)
        losses = []
        for i in range(40):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            params, state, metrics = step(params, state, batch, NO_LIMIT)
            losses.append(float(metrics["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses[::8]

    def test_grad_accum_equivalence(self):
        """grad_accum=4 must match grad_accum=1 on the same global batch."""
        cfg = configs.get_smoke("llama3_2_1b").replace(dtype="float32")
        key = jax.random.PRNGKey(3)
        params = M.init_params(cfg, key)
        opt = adamw(lr=1e-3)
        tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab)
        batch = {"tokens": tokens}

        def one(ga):
            st = opt.init(params)
            step = jax.jit(make_train_step(cfg, opt, grad_accum=ga))
            p2, _, m = step(params, st, batch, NO_LIMIT)
            return m["loss"], p2

        l1, p1 = one(1)
        l4, p4 = one(4)
        np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


class TestResume:
    def test_train_resume_is_deterministic(self, tmp_path):
        """Interrupt-and-resume must land on the same weights as an
        uninterrupted run (checkpoint + seekable data pipeline)."""
        cfg = configs.get_smoke("qwen2_0_5b").replace(dtype="float32")
        key = jax.random.PRNGKey(0)
        opt = adamw(lr=1e-3)
        data = SyntheticLM(vocab=cfg.vocab, seq_len=16, batch=4)
        step = jax.jit(make_train_step(cfg, opt))

        def run(n_steps, params, state, start=0):
            for i in range(start, n_steps):
                batch = {k: jnp.asarray(v)
                         for k, v in data.batch_at(i).items()}
                params, state, _ = step(params, state, batch, NO_LIMIT)
            return params, state

        p0 = M.init_params(cfg, key)
        s0 = opt.init(p0)
        # Uninterrupted 8 steps.
        p_full, _ = run(8, p0, s0)
        # Interrupted: 4 steps, checkpoint, restore, 4 more.
        p_half, s_half = run(4, p0, s0)
        m = CheckpointManager(str(tmp_path), every_steps=1)
        m.save(3, {"params": p_half, "opt": s_half})
        stp, restored, _ = m.restore({"params": p_half, "opt": s_half})
        p_res, _ = run(8, restored["params"], restored["opt"], start=4)
        for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
