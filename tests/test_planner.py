"""Tests for the trace-time DTR planner (jaxpr -> plan -> checkpoint policy)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from repro.core import planner


D = 64
L = 6


def make_params(key):
    ks = jax.random.split(key, L)
    return [dict(w1=jax.random.normal(k, (D, 4 * D)) * 0.02,
                 w2=jax.random.normal(k, (4 * D, D)) * 0.02) for k in ks]


def mlp_fwd(params, x):
    h = x
    for i, p in enumerate(params):
        a = checkpoint_name(jax.nn.gelu(h @ p["w1"]), f"act{i}")
        h = h + checkpoint_name(a @ p["w2"], f"proj{i}")
    return h


def loss_fn(params, x):
    return jnp.mean(mlp_fwd(params, x) ** 2)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = make_params(key)
    # Large batch => activation-dominated graph (realistic training regime).
    x = jax.random.normal(key, (512, D))
    return params, x


def test_trace_to_log_shapes(setup):
    params, x = setup
    tg = planner.trace_to_log(jax.grad(loss_fn), params, x)
    assert tg.log.op_count() > 10
    assert len(tg.named) == 2 * L
    assert tg.total_flops > 0


def test_plan_budget_monotonicity(setup):
    """Lower budgets must evict more named tensors."""
    params, x = setup
    g = jax.grad(loss_fn)
    big = planner.plan(g, params, x, budget_bytes=1e12)
    assert big.feasible and not big.remat_names
    tg = planner.trace_to_log(g, params, x)
    peak = 0  # measure actual sim peak via unconstrained plan
    from repro.core import simulator
    peak, _ = simulator.measure_baseline(tg.log)
    mid = planner.plan(g, params, x, budget_bytes=0.6 * peak)
    low = planner.plan(g, params, x, budget_bytes=0.45 * peak)
    assert mid.feasible
    assert low.feasible
    assert len(low.save_names) <= len(mid.save_names) <= len(big.save_names)
    assert len(low.remat_names) > 0, "tight budget must force remat"
    assert low.est_slowdown >= 1.0


def test_policy_preserves_gradients(setup):
    """jax.checkpoint with the DTR policy must not change numerics."""
    params, x = setup
    g = jax.grad(loss_fn)
    tg = planner.trace_to_log(g, params, x)
    from repro.core import simulator
    peak, _ = simulator.measure_baseline(tg.log)
    p = planner.plan(g, params, x, budget_bytes=0.5 * peak)
    ck_fwd = jax.checkpoint(mlp_fwd, policy=p.policy())

    def ck_loss(params, x):
        return jnp.mean(ck_fwd(params, x) ** 2)

    g_ref = jax.jit(jax.grad(loss_fn))(params, x)
    g_ck = jax.jit(jax.grad(ck_loss))(params, x)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_ck)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_policy_actually_remats(setup):
    """A tight policy should increase compiled FLOPs (recompute visible)."""
    params, x = setup

    def loss_plain(params, x):
        return jnp.mean(mlp_fwd(params, x) ** 2)

    def compiled_flops(policy):
        fwd = jax.checkpoint(mlp_fwd, policy=policy)

        def loss(params, x):
            return jnp.mean(fwd(params, x) ** 2)

        c = jax.jit(jax.grad(loss)).lower(params, x).compile()
        from repro.analysis.hlo import xla_cost_dict
        fa = xla_cost_dict(c.cost_analysis())
        return fa.get("flops", 0.0)

    f_save = compiled_flops(jax.checkpoint_policies.everything_saveable)
    f_none = compiled_flops(jax.checkpoint_policies.nothing_saveable)
    assert f_none > f_save * 1.2, (f_save, f_none)


def test_dtr_checkpoint_end_to_end(setup):
    params, x = setup
    ck, p = planner.dtr_checkpoint(
        lambda pp, xx: mlp_fwd(pp, xx), params, x, budget_bytes=2e5)
    out = jax.jit(ck)(params, x)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()


def test_block_size_planner():
    assert planner.sqrt_block_size(16) == 4
    assert planner.plan_layer_blocks(32, 100.0, 400.0) == 8
    assert planner.plan_layer_blocks(32, 100.0, 1e9) == 1
    assert planner.plan_layer_blocks(32, 100.0, 0.0) == 1
