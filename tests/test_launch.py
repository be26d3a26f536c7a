"""The launch entry points as ``chip_smoke.py`` calls them, at smoke size on
the CPU: what ``train.main`` / ``serve.main`` report, the on-device guard
against bad updates, where the compile cache goes, and the smoke script's
own pieces (CPU refusal, the eager FFN stack)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as cs
from repro import configs
from repro.distributed.monitor import DivergenceGuard
from repro.launch import compile_cache, serve, train
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import layers as L
from repro.models import model as M
from repro.optim import adamw, apply_updates, clip_by_global_norm


@pytest.fixture
def no_repo_cache(monkeypatch, tmp_path):
    """Entry points leave the compile cache to JAX when the variable is
    set; pointing it at a temp dir keeps tests from writing the repo's."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _train(tmp_path, *extra):
    return train.main(["--arch", "smollm-135m", "--smoke", "--steps", "5",
                       "--batch", "2", "--seq", "32",
                       "--ckpt-dir", str(tmp_path / "ckpt"), *extra])


def test_train_main_returns_summary(no_repo_cache, tmp_path):
    s = _train(tmp_path)
    assert s.clean and s.resumed_from is None
    assert s.steps == list(range(5))
    assert len(s.losses) == len(s.step_seconds) == 5
    assert all(math.isfinite(x) for x in s.losses)
    assert s.peak_bytes is None          # the CPU reports no memory stats


def test_train_main_reports_skipped_and_restored_steps(
        no_repo_cache, tmp_path, monkeypatch):
    """A guard that rejects every step after the first: the run says so
    instead of looking like a clean one."""
    monkeypatch.setattr(train, "DivergenceGuard",
                        lambda: DivergenceGuard(spike_factor=1e-9,
                                                max_skips=2))
    s = _train(tmp_path)
    assert not s.clean
    assert s.steps == [0]
    assert s.skipped == [1, 2]
    assert s.restored == [3, 4]


def test_train_step_keeps_state_when_update_rejected():
    cfg = configs.get_smoke("smollm-135m").replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(lr=1e-2)
    state = opt.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab)}
    step = jax.jit(make_train_step(cfg, opt))
    p_rej, s_rej, m_rej = step(params, state, batch, jnp.float32(-1.0))
    p_ok, s_ok, m_ok = step(params, state, batch, jnp.float32(np.inf))
    assert not bool(m_rej["applied"]) and bool(m_ok["applied"])
    for a, b in zip(jax.tree.leaves((p_rej, s_rej)),
                    jax.tree.leaves((params, state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @jax.jit
    def plain_update(params, state):
        grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch))(params)
        grads, _ = clip_by_global_norm(grads, 1.0)
        updates, _ = opt.update(grads, state, params)
        return apply_updates(params, updates)

    for a, b in zip(jax.tree.leaves(p_ok),
                    jax.tree.leaves(plain_update(params, state))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert float(m_rej["loss"]) == float(m_ok["loss"])


def test_divergence_guard_counts_device_verdicts():
    """``record`` takes the step's own verdict: only the counting is on
    the host."""
    g = DivergenceGuard(max_skips=1)
    assert g.record(True, 3.0) == "ok" and g.max_loss() == 30.0
    assert g.record(False, 2.0) == "skip"
    assert g.max_loss() == 30.0          # a rejected loss moves nothing
    assert g.record(False, 2.0) == "restore"
    assert g.record(True, 2.0) == "ok" and g._skips == 0


def test_divergence_guard_max_loss_matches_check():
    g = DivergenceGuard(spike_factor=10.0)
    assert g.max_loss() == math.inf
    assert g.check(2.0, 1.0) == "ok"
    assert g.max_loss() == 20.0
    assert g.check(20.0, 1.0) == "ok"
    assert g.check(np.nextafter(10 * g._ewma_loss, math.inf), 1.0) == "skip"


def test_train_first_loss_matches_plain_reference(no_repo_cache, tmp_path):
    s = _train(tmp_path)
    ref = cs.first_loss_reference("smollm-135m", 2, 32, smoke=True)
    assert s.losses[0] == pytest.approx(ref, rel=1e-5)


def _serve_smoke():
    return serve.main(["--arch", "qwen2-0.5b", "--smoke", "--requests", "6",
                       "--slots", "4", "--gen", "5", "--max-len", "32"])


def test_serve_main_returns_summary(no_repo_cache):
    cfg = configs.get_smoke("qwen2-0.5b")
    s = _serve_smoke()
    assert s.requests == 6 and sorted(s.completed) == list(range(6))
    assert sorted(s.prompts) == list(range(6))
    assert all(len(t) == 5 and all(0 <= x < cfg.vocab for x in t)
               for t in s.completed.values())
    assert s.steps > 0 and s.seconds > 0


def test_served_tokens_are_forward_greedy(no_repo_cache):
    """The serve loop itself, with more requests than slots so that freed
    slots are refilled mid-stream: every generated token is the argmax of
    one float32 forward over the request's prompt and served tokens."""
    cfg = configs.get_smoke("qwen2-0.5b")
    assert cfg.dtype == "float32"
    s = _serve_smoke()
    seqs = [np.concatenate([s.prompts[r], s.completed[r]])
            for r in sorted(s.completed)]
    ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, q in enumerate(seqs):          # right padding: causal, unread
        ids[i, :len(q)] = q
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    greedy = np.asarray(jnp.argmax(
        jax.jit(M.forward, static_argnums=0)(cfg, params, jnp.asarray(ids)),
        axis=-1))
    for i, r in enumerate(sorted(s.completed)):
        p = len(s.prompts[r])
        np.testing.assert_array_equal(greedy[i, p - 1:len(seqs[i]) - 1],
                                      s.completed[r])


def test_served_greedy_replay_agrees_with_serve_loop(no_repo_cache):
    s = _serve_smoke()
    assert cs.served_greedy_replay(s, "qwen2-0.5b", slots=4, max_len=32,
                                   smoke=True) == (0, 6 * 5)


def test_served_greedy_replay_catches_a_shifted_token(no_repo_cache):
    s = _serve_smoke()
    s.completed[3] = s.completed[3][1:] + s.completed[3][:1]
    differ, total = cs.served_greedy_replay(s, "qwen2-0.5b", slots=4,
                                            max_len=32, smoke=True)
    assert differ > 0 and total == 6 * 5


def test_decode_forward_gap_on_served_requests(no_repo_cache):
    gap = cs.decode_forward_gap(_serve_smoke(), "qwen2-0.5b", tokens=8,
                                smoke=True)
    assert gap <= 1e-5


def test_decode_forward_gap_catches_unmasked_cache(no_repo_cache,
                                                   monkeypatch):
    """A decode that attends to cache rows it has not written yet."""
    s = _serve_smoke()
    monkeypatch.setattr(
        L, "decode_mask",
        lambda pos, skv, window=0: jnp.ones((pos.shape[0], 1, 1, 1, skv),
                                            bool))
    gap = cs.decode_forward_gap(s, "qwen2-0.5b", tokens=8, smoke=True)
    assert gap > cs.REF_RTOL


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.REPO_CACHE_DIR.parent.joinpath(
            "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_env_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_host_mesh_over_first_devices():
    assert make_host_mesh(n_devices=1).devices.size == 1
    with pytest.raises(ValueError):
        make_host_mesh(n_devices=len(jax.devices()) + 1)


def test_chip_smoke_refuses_cpu(capsys):
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_eager_phase_remats_with_identical_grads():
    free, tight = cs.eager_phase(blocks=4, d_model=32, d_ff=64, tokens=64,
                                 dtype=jnp.float32)
    assert tight["remat_runs"] > 0 and free["remat_runs"] == 0
    # The executor may overshoot its budget by one op's outputs.
    largest_op = 64 * 64 * 4 * 2
    assert tight["live_high"] <= tight["budget"] + largest_op
    assert tight["live_high"] < free["live_high"]


def test_eager_ffn_grads_match_autodiff():
    blocks, d, f, t = 3, 16, 32, 8
    grads, _ = cs.eager_run(math.inf, blocks=blocks, d_model=d, d_ff=f,
                            tokens=t, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(0), 3 * blocks + 1)
    ws = [(jax.random.normal(ks[3 * i], (d, f)) / math.sqrt(d),
           jax.random.normal(ks[3 * i + 1], (d, f)) / math.sqrt(d),
           jax.random.normal(ks[3 * i + 2], (f, d)) / (math.sqrt(f) * blocks))
          for i in range(blocks)]
    x0 = jax.random.normal(ks[-1], (t, d))

    def loss(ws):
        x = x0
        for wi, wg, wo in ws:
            x = x + cs._act(x @ wg, x @ wi) @ wo
        return 0.5 * jnp.sum(x * x) / t

    want = jax.grad(loss)(ws)
    for got_blk, want_blk in zip(grads, want):
        for a, b in zip(got_blk, want_blk):
            np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5,
                                       atol=1e-6)
