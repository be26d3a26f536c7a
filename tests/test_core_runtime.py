"""Unit + property tests for the DTR core runtime (paper Appendix C semantics)."""
import math

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis is optional: property tests skip, rest run
    from _hypothesis_stub import given, settings, st  # noqa: F401

from repro.core import graphs, simulator
from repro.core.graph import Log, LogBuilder, replay
from repro.core.heuristics import ALL_NAMES, HEStar, by_name, make_ablation
from repro.core.runtime import DTRRuntime, OOMError, ThrashError


def run(log: Log, budget: float, heuristic="h_dtr_eq", **kw) -> DTRRuntime:
    rt = DTRRuntime(budget=budget, heuristic=by_name(heuristic), **kw)
    replay(log, rt)
    return rt


# ---------------------------------------------------------------------------
# Basic engine behaviour
# ---------------------------------------------------------------------------

class TestBasics:
    def test_unconstrained_no_remat(self):
        log = graphs.mlp(depth=4)
        rt = run(log, budget=float("inf"))
        assert rt.remat_ops == 0
        assert rt.total_compute == rt.base_compute

    def test_budget_respected(self):
        log = graphs.mlp(depth=8)
        peak, _ = simulator.measure_baseline(log)
        rt = run(log, budget=0.6 * peak)
        assert rt.peak_memory <= 0.6 * peak + 1e-6

    def test_remat_happens_under_pressure(self):
        log = graphs.mlp(depth=8)
        peak, _ = simulator.measure_baseline(log)
        rt = run(log, budget=0.5 * peak)
        assert rt.evictions > 0
        assert rt.remat_ops > 0
        assert rt.total_compute > rt.base_compute

    def test_oom_below_feasible(self):
        log = graphs.mlp(depth=8)
        with pytest.raises(OOMError):
            run(log, budget=10.0)  # smaller than the constants alone

    def test_constants_never_evicted(self):
        log = graphs.mlp(depth=8)
        peak, _ = simulator.measure_baseline(log)
        rt = run(log, budget=0.5 * peak)
        for s in rt.storages.values():
            if s.constant:
                assert s.resident or s.banished

    def test_output_condition(self):
        """Kept tensors (param grads) must be resident at the end."""
        log = graphs.mlp(depth=8)
        peak, _ = simulator.measure_baseline(log)
        rt = run(log, budget=0.5 * peak)
        for t in rt.tensors.values():
            if t.refs > 0:
                assert t.defined, f"{t.name} not resident at end"

    def test_get_rematerializes(self):
        rt = DTRRuntime(budget=100, heuristic=by_name("h_lru"))
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [40])
        (b,) = rt.call("g", 1.0, [a], [40])
        # Force eviction of a by allocating beyond budget.
        (d,) = rt.call("h", 1.0, [b], [40])
        evicted = [s for s in rt.storages.values()
                   if not s.resident and not s.banished]
        assert evicted, "expected an eviction"
        target = rt.tensors[a]
        if not target.defined:
            rt.get(a)
        assert rt.tensors[a].defined


class TestAliasesAndMutation:
    def test_alias_shares_storage(self):
        rt = DTRRuntime(budget=1000, heuristic=by_name("h_lru"))
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [40])
        (v,) = rt.call("view", 0.1, [a], [0], aliases=[a])
        assert rt.tensors[v].sid == rt.tensors[a].sid
        assert rt.size_of(v) == 0
        # Storage local cost accumulates the view op cost.
        assert rt.storages[rt.tensors[a].sid].local_cost == pytest.approx(1.1)

    def test_alias_evicted_with_storage(self):
        rt = DTRRuntime(budget=95, heuristic=by_name("h_size"))
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [40])
        (v,) = rt.call("view", 0.1, [a], [0], aliases=[a])
        (b,) = rt.call("g", 1.0, [c], [40])  # pressure: evicts a's storage
        s = rt.storages[rt.tensors[a].sid]
        if not s.resident:
            assert not rt.tensors[v].defined
            rt.get(v)  # remat: root then view
            assert rt.tensors[v].defined

    def test_mutation_rewrite(self):
        b = LogBuilder("mut")
        x = b.constant(16, name="x")
        (y,) = b.call([x], [16], 1.0, "f")
        b.mutate([y], [y], 1.0, "add_")
        (z,) = b.call([y], [16], 1.0, "g")
        log = b.auto_release(keep=[z])
        rt = DTRRuntime(budget=1000, heuristic=by_name("h_lru"))
        env = replay(log, rt)
        # y now maps to the post-mutation (copy-on-write) tensor.
        assert rt.tensors[env[y]].name == y + "'"
        assert rt.tensors[env[z]].defined


class TestDeallocPolicies:
    @pytest.mark.parametrize("policy", ["ignore", "eager", "banish"])
    def test_policies_complete(self, policy):
        log = graphs.resnet(blocks=6)
        peak, _ = simulator.measure_baseline(log)
        rt = DTRRuntime(budget=0.7 * peak, heuristic=by_name("h_dtr"),
                        dealloc=policy)
        replay(log, rt)
        assert rt.slowdown() >= 1.0

    def test_eager_eviction_fires(self):
        log = graphs.mlp(depth=6)
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_lru"),
                        dealloc="eager")
        replay(log, rt)
        assert rt.evictions > 0  # releases triggered evictions

    def test_banish_frees_permanently(self):
        rt = DTRRuntime(budget=1000, heuristic=by_name("h_lru"),
                        dealloc="banish")
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [40])
        (b,) = rt.call("g", 1.0, [a], [40])
        rt.release(a)  # no evicted dependents -> banished
        s = rt.storages[rt.tensors[a].sid]
        assert s.banished
        # Child of banished storage is pinned (non-rematerializable).
        assert rt.storages[rt.tensors[b].sid].pinned

    def test_banish_deferred_with_evicted_dependents(self):
        rt = DTRRuntime(budget=90, heuristic=by_name("h_lru"),
                        dealloc="banish")
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [40])
        (b,) = rt.call("g", 1.0, [a], [40])
        (d,) = rt.call("h", 1.0, [b], [40])  # evicts a or b
        sb = rt.storages[rt.tensors[b].sid]
        if not sb.resident:
            rt.release(b)
            assert not sb.banished  # cannot banish... wait, b itself evicted
        # Release a while b evicted: a has evicted dependent -> deferred.
        sa = rt.storages[rt.tensors[a].sid]
        if sb is not sa and not sb.resident and sa.resident:
            rt.release(a)
            assert not sa.banished
            rt.get(b)  # remat b -> retry banish of a
            assert sa.banished


class TestHeuristics:
    @pytest.mark.parametrize("h", ALL_NAMES)
    def test_all_heuristics_run(self, h):
        log = graphs.transformer(layers=2, d=8, seq=4)
        peak, _ = simulator.measure_baseline(log)
        r = simulator.simulate(log, by_name(h), budget=0.7 * peak)
        assert r.ok
        assert r.slowdown >= 1.0

    def test_dtr_beats_lru_on_low_budget(self):
        """Chain-aware heuristics support budgets where LRU thrashes/OOMs
        (the paper's central empirical claim)."""
        log = graphs.lstm(steps=24)
        peak, _ = simulator.measure_baseline(log)
        frac = 0.4
        r_dtr = simulator.simulate(log, by_name("h_dtr"), budget=frac * peak)
        r_lru = simulator.simulate(log, by_name("h_lru"), budget=frac * peak)
        assert r_dtr.ok
        assert (not r_lru.ok) or r_lru.slowdown >= r_dtr.slowdown

    def test_eq_approximates_full(self):
        """h_DTR^eq stays close to h_DTR (paper Fig. 2 finding)."""
        log = graphs.transformer(layers=4, d=16, seq=8)
        peak, _ = simulator.measure_baseline(log)
        for frac in (0.7, 0.5):
            r_full = simulator.simulate(log, by_name("h_dtr"),
                                        budget=frac * peak)
            r_eq = simulator.simulate(log, by_name("h_dtr_eq"),
                                      budget=frac * peak)
            if r_full.ok and r_eq.ok:
                assert r_eq.slowdown <= r_full.slowdown * 1.5 + 0.1

    def test_eq_fewer_metadata_accesses(self):
        """ẽ* requires far fewer metadata accesses than exact e* (App. D.3)."""
        log = graphs.treelstm(depth=5)
        peak, _ = simulator.measure_baseline(log)
        r_full = simulator.simulate(log, by_name("h_dtr"), budget=0.5 * peak)
        r_eq = simulator.simulate(log, by_name("h_dtr_eq"), budget=0.5 * peak)
        r_local = simulator.simulate(log, by_name("h_dtr_local"),
                                     budget=0.5 * peak)
        assert r_full.ok and r_eq.ok
        assert r_eq.meta_accesses < r_full.meta_accesses
        if r_local.ok:
            assert r_local.meta_accesses < r_eq.meta_accesses

    def test_ablation_grid_instantiates(self):
        log = graphs.mlp(depth=4)
        peak, _ = simulator.measure_baseline(log)
        for stale in (True, False):
            for mem in (True, False):
                for cost in ("estar", "eq", "local", "no"):
                    h = make_ablation(stale, mem, cost)
                    r = simulator.simulate(log, h, budget=0.8 * peak)
                    assert r.ok, h.name

    def test_sampling_and_small_filters(self):
        log = graphs.resnet(blocks=8)
        peak, _ = simulator.measure_baseline(log)
        r = simulator.simulate(log, by_name("h_dtr_eq"), budget=0.6 * peak,
                               ignore_small_frac=0.01, sample_sqrt=True)
        assert r.ok


# ---------------------------------------------------------------------------
# Formal bounds (Sec. 3)
# ---------------------------------------------------------------------------

class TestTheorems:
    @pytest.mark.parametrize("n", [100, 400, 900])
    def test_thm31_linear_ops_within_constant_factor(self, n):
        """DTR with h_e* and B = 2⌈√N⌉ executes O(N) ops (Thm 3.1)."""
        log = graphs.linear_network(n)
        b = 2 * math.ceil(math.sqrt(n))
        rt = DTRRuntime(budget=b, heuristic=HEStar())
        replay(log, rt)
        # 2N base ops (fwd+bwd); overhead must be a constant factor.
        assert rt.ops_executed <= 6 * n, (
            f"N={n}: {rt.ops_executed} ops exceeds 6N")

    def test_thm31_scaling_is_linear(self):
        """ops/N should not grow with N (constant-factor check)."""
        ratios = []
        for n in (200, 800, 1800):
            log = graphs.linear_network(n)
            b = 2 * math.ceil(math.sqrt(n))
            rt = DTRRuntime(budget=b, heuristic=HEStar())
            replay(log, rt)
            ratios.append(rt.ops_executed / n)
        assert ratios[-1] <= ratios[0] * 1.5 + 0.5

    def test_thm32_adversarial_blowup(self):
        """The adversary forces superlinear work (Thm 3.2)."""
        n, b = 240, 8
        rt = DTRRuntime(budget=b + 1, heuristic=by_name("h_lru"))
        ops = graphs.AdversarialDriver(n, b).run(rt)
        # Theoretical lower bound ~ N^2/(4B); check clear superlinearity.
        assert ops > 3 * n


# ---------------------------------------------------------------------------
# Property tests: random DAGs
# ---------------------------------------------------------------------------

#: The thrash bound ``simulator.simulate`` applies (``thrash_factor``): a
#: run whose compute passes 50x the baseline stops with ``ThrashError``.
THRASH_FACTOR = 50


class TestProperties:
    @settings(max_examples=25, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 10_000), n_ops=st.integers(5, 40),
           frac=st.floats(0.4, 1.0))
    def test_random_dag_invariants(self, seed, n_ops, frac):
        """For any DAG/budget: if the run completes, (1) peak memory within
        budget, (2) compute >= baseline, (3) kept tensors resident,
        (4) constants never evicted."""
        log = graphs.random_dag(n_ops, seed=seed)
        peak, base_cost = simulator.measure_baseline(log)
        rt = DTRRuntime(budget=frac * peak, heuristic=by_name("h_dtr_eq"),
                        compute_limit=THRASH_FACTOR * base_cost)
        try:
            replay(log, rt)
        except (OOMError, ThrashError):
            return  # infeasible or thrashing budgets are legal outcomes
        assert rt.peak_memory <= frac * peak + 1e-6
        assert rt.total_compute >= base_cost - 1e-6
        for t in rt.tensors.values():
            if t.refs > 0:
                assert t.defined
        for s in rt.storages.values():
            if s.constant and not s.banished:
                assert s.resident

    def test_thrash_bound_ends_runaway_draw(self):
        """A draw that thrashes for 285,000+ ops without a compute limit
        stops within a few thousand under the thrash bound."""
        log = graphs.random_dag(30, seed=8535)
        peak, base_cost = simulator.measure_baseline(log)
        rt = DTRRuntime(budget=0.622 * peak, heuristic=by_name("h_dtr_eq"),
                        compute_limit=THRASH_FACTOR * base_cost)
        with pytest.raises(ThrashError):
            replay(log, rt)
        assert rt.ops_executed < 10_000

    @settings(max_examples=15, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 10_000), n_ops=st.integers(5, 30))
    def test_unconstrained_matches_baseline(self, seed, n_ops):
        """With infinite budget and 'ignore' dealloc, no op ever re-runs."""
        log = graphs.random_dag(n_ops, seed=seed)
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_lru"),
                        dealloc="ignore")
        replay(log, rt)
        assert rt.remat_ops == 0

    @settings(max_examples=15, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 10_000), frac=st.floats(0.3, 0.9))
    def test_heuristics_agree_on_feasibility_ordering(self, seed, frac):
        """All heuristics complete or OOM; compute is finite when ok."""
        log = graphs.random_dag(25, seed=seed)
        peak, _ = simulator.measure_baseline(log)
        for h in ("h_dtr_eq", "h_lru", "h_size"):
            r = simulator.simulate(log, by_name(h), budget=frac * peak)
            if r.ok:
                assert math.isfinite(r.slowdown)


# ---------------------------------------------------------------------------
# Log serialization round-trip
# ---------------------------------------------------------------------------

def test_log_roundtrip():
    log = graphs.transformer(layers=2, d=8, seq=4)
    text = log.dumps()
    log2 = Log.loads(text, name=log.name)
    assert len(log2) == len(log)
    r1 = simulator.simulate(log, by_name("h_dtr_eq"), budget=float("inf"))
    r2 = simulator.simulate(log2, by_name("h_dtr_eq"), budget=float("inf"))
    assert r1.compute == r2.compute
