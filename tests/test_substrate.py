"""Substrate tests: checkpointing, fault tolerance, data, collectives,
sharding rules, optimizers."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, restore_latest, save_checkpoint
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.distributed.collectives import (
    compressed_psum, dequantize_int8, quantize_int8)
from repro.distributed.monitor import DivergenceGuard, StragglerMonitor
from repro.distributed.sharding import (
    LOGICAL_RULES, ParamInfo, mesh_context, param_pspec, pspec)
from repro.launch.mesh import make_host_mesh
from repro.optim import (adafactor, adamw, apply_updates,
                         clip_by_global_norm, cosine_schedule, sgdm)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

class TestCheckpoint:
    def tree(self, scale=1.0):
        return {"a": jnp.arange(8.0) * scale,
                "b": {"w": jnp.ones((4, 4)) * scale,
                      "s": jnp.zeros((), jnp.int32) + int(scale)}}

    def test_save_restore_roundtrip(self, tmp_path):
        t = self.tree(2.0)
        save_checkpoint(str(tmp_path), 7, t, extra={"cursor": 7})
        step, restored, extra = restore_latest(str(tmp_path), self.tree(0.0))
        assert step == 7
        assert extra["cursor"] == 7
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_wins_and_retention(self, tmp_path):
        m = CheckpointManager(str(tmp_path), every_steps=1, keep=2)
        for s in range(5):
            m.save(s, self.tree(float(s)))
        dirs = sorted(os.listdir(tmp_path))
        assert len(dirs) == 2  # retention
        step, restored, _ = m.restore(self.tree(0.0))
        assert step == 4
        assert float(restored["a"][1]) == 4.0

    def test_atomicity_no_partial_dirs(self, tmp_path):
        m = CheckpointManager(str(tmp_path), every_steps=1, keep=3)
        m.save(0, self.tree())
        # temp dirs must never remain
        assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]

    def test_elastic_restore_different_sharding(self, tmp_path):
        """Restore works regardless of device layout (arrays are logical)."""
        t = self.tree(3.0)
        save_checkpoint(str(tmp_path), 1, t)
        # Simulate a different-device-count job: plain restore + device_put.
        step, restored, _ = restore_latest(str(tmp_path), self.tree(0.0))
        out = jax.device_put(restored["b"]["w"], jax.devices()[0])
        np.testing.assert_array_equal(np.asarray(out), np.ones((4, 4)) * 3)


# ---------------------------------------------------------------------------
# Fault tolerance monitors
# ---------------------------------------------------------------------------

class TestMonitors:
    def test_straggler_flags_outlier(self):
        fired = []
        mon = StragglerMonitor(threshold=2.0, patience=2,
                               on_straggler=fired.append)
        for i in range(10):
            mon.record(i, 0.1)
        mon.record(10, 0.5)
        mon.record(11, 0.5)
        assert any(s.flagged for s in mon.history)
        assert fired, "straggler callback should fire after patience"

    def test_straggler_ewma_robust(self):
        mon = StragglerMonitor()
        for i in range(5):
            mon.record(i, 0.1)
        mon.record(5, 10.0)  # outlier not folded into ewma
        assert mon.ewma < 0.2

    def test_divergence_guard(self):
        g = DivergenceGuard(spike_factor=10.0, max_skips=2)
        assert g.check(1.0, 1.0) == "ok"
        assert g.check(1.1, 1.0) == "ok"
        assert g.check(float("nan"), 1.0) == "skip"
        assert g.check(float("nan"), 1.0) == "skip"
        assert g.check(float("nan"), 1.0) == "restore"
        assert g.check(1.0, 1.0) == "ok"  # recovers


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

class TestData:
    def test_deterministic_and_seekable(self):
        d = SyntheticLM(vocab=512, seq_len=32, batch=4, seed=3)
        b1 = d.batch_at(10)
        b2 = d.batch_at(10)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(d.batch_at(11)["tokens"], b1["tokens"])

    def test_learnable_structure(self):
        """Even positions determine odd positions via a fixed permutation."""
        d = SyntheticLM(vocab=128, seq_len=16, batch=8, seed=0)
        b = d.batch_at(0)["tokens"]
        perm_rng = np.random.default_rng(0)
        perm = perm_rng.permutation(128)
        np.testing.assert_array_equal(b[:, 1::2], perm[b[:, 0::2]])

    def test_prefetcher(self):
        d = SyntheticLM(vocab=64, seq_len=8, batch=2)
        pf = Prefetcher(d, start_step=5)
        s, b = pf.next()
        assert s == 5
        np.testing.assert_array_equal(b["tokens"], d.batch_at(5)["tokens"])
        pf.stop()

    def test_codebook_shape(self):
        d = SyntheticLM(vocab=64, seq_len=8, batch=2, n_codebooks=4)
        assert d.batch_at(0)["tokens"].shape == (2, 8, 4)


# ---------------------------------------------------------------------------
# Collectives / compression
# ---------------------------------------------------------------------------

class TestCompression:
    def test_quantize_roundtrip_error(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1024,))
        q, s = quantize_int8(x)
        err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
        assert float(err) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6

    def test_compressed_psum_single_device(self):
        """Degenerate 1-device psum must be ~identity (quantization only)."""
        mesh = make_host_mesh()

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        x = jax.random.normal(jax.random.PRNGKey(1), (64,))

        def f(v):
            return compressed_psum({"g": v}, ("data",))["g"]

        out = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   atol=float(jnp.max(jnp.abs(x))) / 100)


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

class TestSharding:
    def test_pspec_resolution_and_dedup(self):
        mesh = make_host_mesh()
        with mesh_context(mesh):
            s = pspec("batch", None, "mlp")
            assert len(s) == 3

    def test_divisibility_fit(self):
        mesh = make_host_mesh()  # 1 device -> (1,1) mesh
        with mesh_context(mesh, overrides={"heads": "model"}):
            # heads=3 over model axis size 1 -> trivially ok; shape-aware
            s = pspec("heads", shape=(3,))
            assert s is not None

    def test_param_pspec_fsdp(self):
        mesh = make_host_mesh()
        info = ParamInfo((8, 4), "float32", (None, "mlp"), fsdp_dim=0)
        with mesh_context(mesh, fsdp=True):
            s = param_pspec(info)
            assert len(s) == 2

    def test_no_mesh_noop(self):
        x = jnp.ones((4, 4))
        from repro.distributed.sharding import shard
        assert shard(x, "batch", "embed") is x


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

class TestOptim:
    def quad(self, opt, steps=60):
        params = {"w": jnp.array([3.0, -2.0])}
        state = opt.init(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        for _ in range(steps):
            g = jax.grad(loss)(params)
            upd, state = opt.update(g, state, params)
            params = apply_updates(params, upd)
        return float(loss(params))

    def test_adamw_converges(self):
        assert self.quad(adamw(lr=0.1, weight_decay=0.0)) < 0.1

    def test_adafactor_converges(self):
        assert self.quad(adafactor(lr=0.3)) < 0.5

    def test_sgdm_converges(self):
        assert self.quad(sgdm(lr=0.05)) < 0.1

    def test_adafactor_factored_state_is_small(self):
        p = {"w": jnp.ones((64, 32))}
        st = adafactor().init(p)
        sizes = [np.prod(x.shape) for x in jax.tree.leaves(st.inner)]
        assert max(sizes) <= 64  # factored: no [64,32] second moment

    def test_clip_by_global_norm(self):
        g = {"a": jnp.full((10,), 10.0)}
        clipped, gn = clip_by_global_norm(g, 1.0)
        assert float(gn) > 1.0
        total = jnp.sqrt(sum(jnp.sum(x ** 2)
                             for x in jax.tree.leaves(clipped)))
        assert float(total) <= 1.0 + 1e-5

    def test_cosine_schedule(self):
        lr = cosine_schedule(1.0, warmup=10, total=100)
        assert float(lr(jnp.int32(0))) == 0.0
        assert float(lr(jnp.int32(10))) == pytest.approx(1.0)
        assert float(lr(jnp.int32(100))) == pytest.approx(0.1, abs=0.01)
