"""Tests for the eager DTR executor: real buffers, real eviction, real remat."""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.eager import DTRContext, DTRArray, executor, op


def test_basic_chain_correctness():
    ctx = DTRContext(budget_bytes=float("inf"))
    x = ctx.wrap(jnp.arange(16.0))
    y = ctx.call("sin", jnp.sin, [x])[0]
    z = ctx.call("sum", jnp.sum, [y])[0]
    np.testing.assert_allclose(z.value, np.sin(np.arange(16.0)).sum(),
                               rtol=1e-6)


def test_eviction_and_remat_preserve_values():
    """Run a chain under a tight budget; every value must still be exact."""
    n = 64 * 1024 // 4  # 64 KiB fp32 tensors
    budget = 5 * 64 * 1024  # room for ~5 tensors
    ctx = DTRContext(budget_bytes=budget)
    x = ctx.wrap(jnp.linspace(0, 1, n))
    vals = [x]
    for i in range(20):
        vals.append(ctx.call(f"f{i}", lambda a: jnp.cos(a) * 1.01, [vals[-1]])[0])
    assert ctx.rt.evictions > 0, "budget should have forced evictions"
    # Access an early intermediate: must rematerialize correctly.
    expect = np.linspace(0, 1, n)
    for i in range(1, 6):
        expect = np.cos(expect) * 1.01
    np.testing.assert_allclose(np.asarray(vals[5].value), expect, rtol=1e-5)
    assert ctx.remat_runs > 0


def test_budget_respected_in_real_bytes():
    n = 32 * 1024 // 4
    budget = 6 * 32 * 1024
    ctx = DTRContext(budget_bytes=budget)
    x = ctx.wrap(jnp.ones(n))
    h = x
    for i in range(30):
        h = ctx.call(f"g{i}", lambda a: a * 1.0001, [h])[0]
        # One-allocation slack allowed (paper App. E.1).
        assert ctx.live_bytes() <= budget + 32 * 1024
    assert jnp.isfinite(h.value).all()


def test_dynamic_control_flow_treelstm_style():
    """Data-dependent recursion (the paper's dynamic-model headline)."""
    dim = 256
    # Budget: pinned constants (weight matrix + 16 leaves) + ~10 activation
    # slots; the ~30 internal activations must be evicted/rematerialized.
    budget = (dim * dim + 16 * dim + 10 * dim) * 4
    ctx = DTRContext(budget_bytes=budget)
    w = ctx.wrap(jnp.eye(dim) * 0.5 + 0.01, name="w")

    def cell(a: DTRArray, b: DTRArray) -> DTRArray:
        s = ctx.call("add", jnp.add, [a, b])[0]
        return ctx.call("cell", lambda s_, w_: jnp.tanh(s_ @ w_), [s, w])[0]

    def build(depth: int, leaf_val: float) -> DTRArray:
        if depth == 0:
            return ctx.wrap(jnp.full((dim,), leaf_val), name="leaf")
        left = build(depth - 1, leaf_val)
        right = build(depth - 1, leaf_val + 0.1)
        return cell(left, right)

    root = build(4, 0.05)
    v = root.value
    assert v.shape == (dim,)
    assert bool(jnp.isfinite(v).all())
    assert ctx.rt.evictions > 0


def test_multi_output_ops():
    ctx = DTRContext(budget_bytes=float("inf"))
    x = ctx.wrap(jnp.arange(8.0))
    outs = ctx.call("split", lambda a: (a[:4], a[4:]), [x])
    assert len(outs) == 2
    np.testing.assert_allclose(outs[1].value, np.arange(4.0) + 4)


def test_op_helper_and_arith_sugar():
    ctx = DTRContext(budget_bytes=float("inf"))
    gelu = op(ctx, "gelu", jax.nn.gelu)
    x = ctx.wrap(jnp.ones((4, 4)))
    y = gelu(x + x)
    z = y @ x
    assert z.value.shape == (4, 4)


def test_training_loop_under_budget():
    """A tiny MLP training step with manual backward passes through DTR."""
    key = jax.random.PRNGKey(0)
    din, dh, n = 64, 256, 32
    budget = 40 * n * dh * 4
    ctx = DTRContext(budget_bytes=budget)
    w1 = ctx.wrap(jax.random.normal(key, (din, dh)) * 0.05, name="w1")
    w2 = ctx.wrap(jax.random.normal(key, (dh, 1)) * 0.05, name="w2")
    xb = ctx.wrap(jax.random.normal(key, (n, din)), name="x")
    yb = ctx.wrap(jnp.ones((n, 1)), name="y")

    losses = []
    lr = 0.05
    for step in range(4):
        h = ctx.call("fc1", jnp.matmul, [xb, w1])[0]
        a = ctx.call("relu", jax.nn.relu, [h])[0]
        p = ctx.call("fc2", jnp.matmul, [a, w2])[0]
        e = ctx.call("err", jnp.subtract, [p, yb])[0]
        loss = ctx.call("mse", lambda t: jnp.mean(t * t), [e])[0]
        # Manual backward (each op goes through DTR as well).
        gp = ctx.call("d_mse", lambda t: 2 * t / t.size, [e])[0]
        gw2 = ctx.call("d_w2", lambda a_, g: a_.T @ g, [a, gp])[0]
        ga = ctx.call("d_a", lambda g, w: g @ w.T, [gp, w2])[0]
        gh = ctx.call("d_relu", lambda g, h_: g * (h_ > 0), [ga, h])[0]
        gw1 = ctx.call("d_w1", lambda x_, g: x_.T @ g, [xb, gh])[0]
        w1 = ctx.call("sgd1", lambda w, g: w - lr * g, [w1, gw1])[0]
        w2 = ctx.call("sgd2", lambda w, g: w - lr * g, [w2, gw2])[0]
        losses.append(float(loss.value))
    assert losses[-1] < losses[0], f"no learning: {losses}"


# ---------------------------------------------------------------------------
# Costs per op signature and the bounded in-flight window
# ---------------------------------------------------------------------------

def test_same_signature_timed_once():
    ctx = DTRContext(budget_bytes=float("inf"))
    h = ctx.wrap(jnp.ones(256))
    for _ in range(20):
        h = ctx.call("scale", lambda a: a * 1.5, [h])[0]
    assert ctx.timed_calls == 1
    costs = {o.cost for o in ctx.rt.ops.values()}
    assert len(costs) == 1 and costs.pop() > 0


def test_distinct_names_shapes_and_dtypes_each_timed_once():
    ctx = DTRContext(budget_bytes=float("inf"))
    xs = [ctx.wrap(jnp.ones(8)), ctx.wrap(jnp.ones(16)),
          ctx.wrap(jnp.ones(8, jnp.int32))]
    for _ in range(3):
        for x in xs:
            ctx.call("neg", jnp.negative, [x])
            ctx.call("abs", jnp.abs, [x])
        ctx.call("add", jnp.add, [xs[0], xs[0]])
        ctx.call("add", jnp.add, [xs[0], 2.0])   # one input: new signature
    assert ctx.timed_calls == 2 * len(xs) + 2


def test_unit_costs_never_sync_to_time(monkeypatch):
    syncs = []
    real = executor.jax.block_until_ready
    monkeypatch.setattr(executor.jax, "block_until_ready",
                        lambda x: syncs.append(1) or real(x))
    ctx = DTRContext(budget_bytes=float("inf"), use_wallclock_cost=False)
    h = ctx.wrap(jnp.ones(64))
    for i in range(executor._MAX_INFLIGHT):
        h = ctx.call(f"f{i}", lambda a: a * 1.5, [h])[0]
    assert syncs == []                  # the window is not yet full
    for i in range(30):
        h = ctx.call(f"g{i}", lambda a: jnp.concatenate([a, a])[:64], [h])[0]
    assert ctx.timed_calls == 0
    assert len(syncs) == ctx.inflight_waits     # only the window waits
    assert {o.cost for o in ctx.rt.ops.values()} == {1.0}
    np.testing.assert_allclose(h.value, np.full(64, 1.5 ** 8), rtol=1e-6)


@pytest.mark.parametrize("wallclock", [False, True])
def test_inflight_window_bounded_after_calls_and_replays(wallclock):
    n = 16 * 1024 // 4
    ctx = DTRContext(budget_bytes=5 * 16 * 1024,
                     use_wallclock_cost=wallclock)
    vals = [ctx.wrap(jnp.linspace(0, 1, n))]
    for i in range(24):
        vals.append(ctx.call("step", lambda a: jnp.cos(a) * 1.01,
                             [vals[-1]])[0])
        assert len(ctx._inflight) <= executor._MAX_INFLIGHT
    assert ctx.rt.evictions > 0
    for i in (3, 11, 7):
        before = ctx.remat_runs
        ctx.fetch(vals[i])
        assert ctx.remat_runs > before, "the fetch should replay"
        assert len(ctx._inflight) <= executor._MAX_INFLIGHT
    expect = np.linspace(0, 1, n)
    for _ in range(7):
        expect = np.cos(expect) * 1.01
    np.testing.assert_allclose(np.asarray(vals[7].value), expect, rtol=1e-5)


def test_first_runs_and_replays_join_the_window(monkeypatch):
    monkeypatch.setattr(executor, "_MAX_INFLIGHT", 10 ** 6)
    n = 16 * 1024 // 4
    ctx = DTRContext(budget_bytes=5 * 16 * 1024, use_wallclock_cost=False)
    vals = [ctx.wrap(jnp.linspace(0, 1, n))]
    for i in range(24):
        vals.append(ctx.call("step", lambda a: jnp.cos(a) * 1.01,
                             [vals[-1]])[0])
    ctx.fetch(vals[5])
    assert ctx.remat_runs > 0
    assert len(ctx._inflight) == 24 + ctx.remat_runs
    assert ctx.inflight_waits == 0


def test_window_keeps_no_dropped_buffer_alive():
    ctx = DTRContext(budget_bytes=float("inf"), use_wallclock_cost=False)
    h = ctx.call("scale", lambda a: a * 1.5, [ctx.wrap(jnp.ones(1024))])[0]
    for _ in range(5):
        nxt = ctx.call("scale", lambda a: a * 1.5, [h])[0]
        h.release()
        h = nxt
    held = [o for refs in ctx._inflight for o in executor._live(refs)]
    assert len(ctx._inflight) == 6
    assert len(held) == 1 and held[0] is ctx.buffers[h.tid]


def _mlp_grads(budget, wallclock):
    """Weight gradients of a 6-layer tanh MLP, forward and manual backward
    through DTR; returns (host gradients, context)."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 7)
    n, d, layers = 64, 128, 6
    ctx = DTRContext(budget_bytes=budget, use_wallclock_cost=wallclock)
    ws = [ctx.wrap(jax.random.normal(ks[i], (d, d)) / d ** 0.5, name="w")
          for i in range(layers)]
    hs = [ctx.wrap(jax.random.normal(ks[-1], (n, d)), name="x")]
    for w in ws:
        hs.append(ctx.call("fc", lambda x, w_: jnp.tanh(x @ w_),
                           [hs[-1], w])[0])
    g = ctx.call("d_loss", lambda y: y / n, [hs[-1]])[0]
    grads = []
    for w, x, y in zip(reversed(ws), reversed(hs[:-1]), reversed(hs[1:])):
        dz = ctx.call("d_tanh", lambda g_, y_: g_ * (1 - y_ * y_), [g, y])[0]
        grads.append(ctx.call("d_w", lambda x_, d_: x_.T @ d_, [x, dz])[0])
        g = ctx.call("d_x", lambda d_, w_: d_ @ w_.T, [dz, w])[0]
    return [np.asarray(t.value) for t in grads], ctx


@pytest.mark.parametrize("wallclock", [False, True])
def test_budgeted_gradients_bit_identical_to_unbudgeted(wallclock):
    free, _ = _mlp_grads(float("inf"), wallclock)
    act = 64 * 128 * 4
    pinned = 6 * 128 * 128 * 4 + act
    got, ctx = _mlp_grads(pinned + 5 * act, wallclock)
    assert ctx.rt.evictions > 0 and ctx.remat_runs > 0
    assert ctx.timed_calls == (5 if wallclock else 0)   # one per op name
    for a, b in zip(free, got):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# One compiled program per op signature, shared across contexts
# ---------------------------------------------------------------------------

@pytest.fixture
def programs(monkeypatch):
    """A fresh process-wide program cache for one test."""
    cache = executor._Programs()
    monkeypatch.setattr(executor, "_PROGRAMS", cache)
    return cache


def test_fresh_lambda_compiles_once_across_contexts(programs):
    n = 16 * 1024 // 4
    for _ in range(2):
        ctx = DTRContext(budget_bytes=5 * 16 * 1024)
        vals = [ctx.wrap(jnp.linspace(0, 1, n))]
        for i in range(24):      # a new lambda and a new name every call
            vals.append(ctx.call(f"step{i}", lambda a: jnp.cos(a) * 1.01,
                                 [vals[-1]])[0])
        ctx.fetch(vals[5])
        assert ctx.remat_runs > 0
        assert ctx.compiled_calls == 24 + ctx.remat_runs
        assert ctx.uncompiled_calls == 0
    assert programs.compiles == 1
    expect = np.linspace(0, 1, n)
    for _ in range(5):
        expect = np.cos(expect) * 1.01
    np.testing.assert_allclose(np.asarray(vals[5].value), expect, rtol=1e-5)


@pytest.mark.parametrize("lr1, lr2, dtype", [
    (0.5, 0.25, jnp.float32),
    (2, 2.0, jnp.int32),         # 2 keeps int32, 2.0 makes float32
    (0.0, -0.0, jnp.float32),    # equal, yet -0.0 - 0.0 != -0.0 + 0.0
])
def test_closures_differing_in_a_captured_scalar_compile_apart(programs, lr1,
                                                               lr2, dtype):
    w0 = jnp.asarray([-0.0, 1.0, 2.0, 3.0]).astype(dtype)
    g0 = jnp.full(4, 3, dtype)
    ctx = DTRContext(budget_bytes=float("inf"))
    w, g = ctx.wrap(w0), ctx.wrap(g0)

    def sgd(lr):
        return lambda w, g: w - lr * g

    for lr in (lr1, lr2):
        got = ctx.call("sgd", sgd(lr), [w, g])[0].value
        want = w0 - lr * g0
        assert got.dtype == want.dtype
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert programs.compiles == 2 and ctx.compiled_calls == 2


def test_untraceable_fn_falls_back_and_is_not_retraced(programs):
    traces = []
    real = programs._compile
    programs._compile = lambda *a: traces.append(1) or real(*a)

    def via_numpy(a):
        return jnp.asarray(np.asarray(a) * 2)

    ctx = DTRContext(budget_bytes=float("inf"))
    x = ctx.wrap(jnp.arange(4.0))
    for _ in range(2):
        y = ctx.call("double", via_numpy, [x])[0]
        np.testing.assert_array_equal(y.value, np.arange(4.0) * 2)
    assert traces == [1] and programs.compiles == 0
    assert ctx.uncompiled_calls == 2 and ctx.compiled_calls == 0


class _Scale:
    def __init__(self, s):
        self.s = s

    def apply(self, a):
        return a * self.s


@pytest.mark.parametrize("make_fn", [
    lambda: (lambda w: lambda a: a * w)(np.arange(4.0)),   # over an array
    lambda: (lambda s: lambda a: a * s[0])([2.0]),         # over a list
    lambda: functools.partial(jnp.multiply, 2.0),          # a fresh partial
    lambda: _Scale(2.0).apply,                             # a bound method
], ids=["array", "list", "partial", "bound_method"])
def test_uncacheable_fn_falls_back(programs, make_fn):
    ctx = DTRContext(budget_bytes=float("inf"))
    x = ctx.wrap(jnp.arange(4.0))
    fn = make_fn()
    y = ctx.call("scale", fn, [x])[0]
    np.testing.assert_array_equal(y.value, fn(jnp.arange(4.0)))
    assert ctx.uncompiled_calls == 1 and ctx.compiled_calls == 0
    assert programs.compiles == 0 and not programs.entries


def test_first_call_cost_excludes_compile_time(programs):
    real = programs._compile

    def slow_compile(*a):
        time.sleep(0.5)
        return real(*a)

    programs._compile = slow_compile
    ctx = DTRContext(budget_bytes=float("inf"))
    ctx.call("scale", lambda a: a * 1.5, [ctx.wrap(jnp.ones(256))])
    assert ctx.timed_calls == 1 and programs.compiles == 1
    (cost,) = {o.cost for o in ctx.rt.ops.values()}
    assert 0 < cost < 0.25


def test_program_cache_is_bounded_and_ops_keep_their_programs(programs,
                                                              monkeypatch):
    monkeypatch.setattr(executor, "_MAX_PROGRAMS", 2)
    n = 16 * 1024 // 4
    ctx = DTRContext(budget_bytes=5 * 16 * 1024)
    vals = [ctx.wrap(jnp.linspace(0, 1, n))]
    for i in range(12):              # three programs, cycled
        k = 1.0 + (i % 3) / 100
        vals.append(ctx.call("step", lambda a, k=k: jnp.cos(a) * k,
                             [vals[-1]])[0])
        assert len(programs.entries) <= 2
    ctx.fetch(vals[6])
    assert ctx.remat_runs > 0 and ctx.uncompiled_calls == 0
    expect = np.linspace(0, 1, n)
    for i in range(6):
        expect = np.cos(expect) * (1.0 + (i % 3) / 100)
    np.testing.assert_allclose(np.asarray(vals[6].value), expect, rtol=1e-5)


def test_closure_over_a_changing_value_stops_compiling(programs):
    ctx = DTRContext(budget_bytes=float("inf"))
    x = ctx.wrap(jnp.arange(4.0))
    for step in range(10):
        err = 0.1 * step         # a new value every step, as a loss is
        y = ctx.call("scale", lambda a: err * a, [x])[0]
        np.testing.assert_array_equal(y.value, err * jnp.arange(4.0))
    assert programs.compiles == executor._MAX_VARIANTS
    assert ctx.compiled_calls == executor._MAX_VARIANTS
    assert ctx.uncompiled_calls == 10 - executor._MAX_VARIANTS
