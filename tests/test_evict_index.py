"""Tests for the incremental eviction index (repro.core.evict_index).

The load-bearing property is *oracle equivalence*: with the index enabled
(default) the runtime must make bit-for-bit the same eviction decisions as
the exhaustive linear scan (``index=False``) — same evictions, same
rematerializations, same compute, same peak memory — across every
heuristic, deallocation policy, memory model, and seed log.  Only
``meta_accesses`` may (and should) differ: that is the point.
"""
import warnings

import pytest

from repro.core import graphs, simulator
from repro.core.evict_index import EvictIndex, ScopedInvalidator
from repro.core.graph import replay
from repro.core.heuristics import ALL_NAMES, by_name, window_cost
from repro.core.runtime import DTRRuntime

# Every RunResult field except meta_accesses (which legitimately differs).
PARITY_FIELDS = ("budget", "ok", "slowdown", "compute", "base_compute",
                 "evictions", "remat_ops", "ops_executed", "peak_memory",
                 "error", "largest_free", "frag_ratio", "failed_fits",
                 "evict_windows")


def assert_parity(a, b, ctx=""):
    for f in PARITY_FIELDS:
        assert getattr(a, f) == getattr(b, f), f"{ctx}: {f} differs"


def both(log, heuristic, budget, **kw):
    a = simulator.simulate(log, heuristic, budget=budget, index=False, **kw)
    b = simulator.simulate(log, heuristic, budget=budget, index=True, **kw)
    return a, b


# ---------------------------------------------------------------------------
# Oracle equivalence
# ---------------------------------------------------------------------------

LOGS = [
    lambda: graphs.mlp(depth=8),
    lambda: graphs.random_dag(40, seed=3),
    lambda: graphs.linear_network(80),
]


class TestOracleEquivalence:
    @pytest.mark.parametrize("heuristic", ALL_NAMES + ["h_estar"])
    @pytest.mark.parametrize("dealloc", ["ignore", "eager", "banish"])
    def test_counter_mode(self, heuristic, dealloc):
        for mk in LOGS:
            log = mk()
            peak, _ = simulator.measure_baseline(log)
            for frac in (0.8, 0.5):
                a, b = both(log, heuristic, frac * peak, dealloc=dealloc)
                assert_parity(a, b, f"{log.name}/{heuristic}/{dealloc}/{frac}")

    @pytest.mark.parametrize("heuristic",
                             ["h_dtr", "h_dtr_eq", "h_lru", "h_size"])
    @pytest.mark.parametrize("dealloc", ["eager", "banish"])
    def test_pool_mode(self, heuristic, dealloc):
        """Window eviction must pick identical windows through the index's
        shared score cache (alloc_mode=pool)."""
        log = graphs.mlp(depth=8)
        peak, _ = simulator.measure_baseline(log)
        for frac in (0.7, 0.5):
            a, b = both(log, heuristic, frac * peak, dealloc=dealloc,
                        alloc_mode="pool")
            assert_parity(a, b, f"pool/{heuristic}/{dealloc}/{frac}")

    def test_pool_nofrag_mode(self):
        log = graphs.mlp(depth=8)
        peak, _ = simulator.measure_baseline(log)
        a, b = both(log, "h_dtr_eq", 0.6 * peak, alloc_mode="pool_nofrag")
        assert_parity(a, b, "pool_nofrag")

    def test_meta_accesses_reduced_on_chain(self):
        """The index must do strictly less metadata work than the scan on a
        pressure-heavy chain (the App. C.5/D.3 overhead it exists to cut)."""
        log = graphs.linear_network(300)
        peak, _ = simulator.measure_baseline(log)
        for h in ("h_dtr", "h_dtr_eq", "h_lru"):
            a, b = both(log, h, 0.3 * peak)
            assert_parity(a, b, h)
            assert b.meta_accesses < a.meta_accesses, h

    def test_models_equivalent(self):
        for log in (graphs.resnet(blocks=6),
                    graphs.transformer(layers=2, d=8, seq=4),
                    graphs.treelstm(depth=4)):
            peak, _ = simulator.measure_baseline(log)
            a, b = both(log, "h_dtr", 0.6 * peak)
            assert_parity(a, b, log.name)


# ---------------------------------------------------------------------------
# Index internals
# ---------------------------------------------------------------------------

class TestIndexInternals:
    def test_nonseparable_falls_back_to_scan(self):
        rt = DTRRuntime(budget=100, heuristic=by_name("h_rand"))
        assert rt.index is None

    def test_sampling_modes_fall_back_to_scan(self):
        rt = DTRRuntime(budget=100, heuristic=by_name("h_dtr"),
                        sample_sqrt=True)
        assert rt.index is None
        rt = DTRRuntime(budget=100, heuristic=by_name("h_dtr"),
                        ignore_small_frac=0.1)
        assert rt.index is None

    def test_index_opt_out(self):
        rt = DTRRuntime(budget=100, heuristic=by_name("h_dtr"), index=False)
        assert rt.index is None

    def test_membership_tracks_evictability(self):
        """The live set must equal the scan's candidate filter at any time."""
        log = graphs.mlp(depth=6)
        peak, _ = simulator.measure_baseline(log)
        rt = DTRRuntime(budget=0.6 * peak, heuristic=by_name("h_dtr_eq"))
        orig = EvictIndex.pick
        checked = [0]

        def checking_pick(self, exclude):
            truth = {s.sid for s in rt.storages.values()
                     if s.evictable() and s.size > 0}
            assert truth == self.members
            checked[0] += 1
            return orig(self, exclude)

        EvictIndex.pick = checking_pick
        try:
            replay(log, rt)
        finally:
            EvictIndex.pick = orig
        assert checked[0] > 0

    def test_pick_matches_linear_argmin(self):
        """Direct spot-check: index.pick == scan argmin on a live runtime."""
        log = graphs.random_dag(30, seed=7)
        peak, _ = simulator.measure_baseline(log)
        rt = DTRRuntime(budget=0.5 * peak, heuristic=by_name("h_dtr"))
        orig = EvictIndex.pick

        def checking_pick(self, exclude):
            got = orig(self, exclude)
            pool = rt._candidates(exclude)
            want = min(
                ((rt.heuristic.score(rt, s), s.sid) for s in pool),
                default=None)
            if want is None:
                assert got is None
            else:
                assert got is not None
                assert (rt.heuristic.score(rt, got), got.sid) == want
            return got

        EvictIndex.pick = checking_pick
        try:
            from repro.core.runtime import OOMError
            try:
                replay(log, rt)
            except OOMError:
                pass  # infeasible budget is a legal outcome; checks ran
        finally:
            EvictIndex.pick = orig

    def test_band_floor_is_admissible(self):
        for k in (1e-9, 0.3, 0.5, 0.99, 1.0, 1.5, 2.0, 3.14159, 1e6,
                  2.0 ** -0.75, 2.0 ** -0.5, 7.0 / 3.0):
            b = EvictIndex._band_of(k)
            idx = DTRRuntime(budget=1, heuristic=by_name("h_dtr")).index
            assert idx._floor_of(b) <= k
            assert idx._floor_of(b + 1) > k
        assert EvictIndex._band_of(0.0) == EvictIndex._ZERO_BAND


class TestScopedInvalidation:
    def test_eviction_only_invalidates_its_component(self):
        """Two disconnected chains: evicting in one must keep the other's
        cached e* entries alive (the global-version nuke is gone)."""
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_dtr"))
        c1, c2 = rt.constant(1), rt.constant(1)
        (a1,) = rt.call("a1", 1.0, [c1], [10])
        (b1,) = rt.call("b1", 1.0, [a1], [10])
        (a2,) = rt.call("a2", 1.0, [c2], [10])
        (b2,) = rt.call("b2", 1.0, [a2], [10])
        sa1, sb1 = rt.tensors[a1].sid, rt.tensors[b1].sid
        sa2, sb2 = rt.tensors[a2].sid, rt.tensors[b2].sid
        # Evict a1, then warm both chains' caches.
        rt._evict(rt.storages[sa1])
        for sid in (sb1, sb2):
            rt.evicted_neighborhood_cost(rt.storages[sid])
        assert sb1 in rt._estar_cache and sb2 in rt._estar_cache
        # Evicting a2 (chain 2) must drop b2's entry but keep b1's.
        rt._evict(rt.storages[sa2])
        assert sb1 in rt._estar_cache
        assert sb2 not in rt._estar_cache

    def test_remat_invalidates_component_consumers(self):
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_dtr"))
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 1.0, [a], [10])
        sa, sb = rt.tensors[a].sid, rt.tensors[b].sid
        rt._evict(rt.storages[sa])
        cost = rt.evicted_neighborhood_cost(rt.storages[sb])
        assert cost == pytest.approx(1.0)
        rt.get(a)  # rematerialize -> b's cached cost must drop
        assert sb not in rt._estar_cache
        assert rt.evicted_neighborhood_cost(rt.storages[sb]) == 0.0

    def test_alias_cost_change_invalidates_consumers(self):
        """Registering a view on an *evicted* storage grows its local cost;
        cached closures that summed it must be dropped."""
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_dtr"))
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 1.0, [a], [10])
        sa, sb = rt.tensors[a].sid, rt.tensors[b].sid
        rt._evict(rt.storages[sa])
        assert rt.evicted_neighborhood_cost(
            rt.storages[sb]) == pytest.approx(1.0)
        rt.call("view", 0.5, [b], [0], aliases=[a])
        assert sb not in rt._estar_cache
        assert rt.evicted_neighborhood_cost(
            rt.storages[sb]) == pytest.approx(1.5)

    def test_eq_cache_scoped(self):
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_dtr_eq"))
        c1, c2 = rt.constant(1), rt.constant(1)
        (a1,) = rt.call("a1", 1.0, [c1], [10])
        (b1,) = rt.call("b1", 1.0, [a1], [10])
        (a2,) = rt.call("a2", 1.0, [c2], [10])
        (b2,) = rt.call("b2", 1.0, [a2], [10])
        sa1, sb1 = rt.tensors[a1].sid, rt.tensors[b1].sid
        sa2, sb2 = rt.tensors[a2].sid, rt.tensors[b2].sid
        rt._evict(rt.storages[sa1])
        rt._evict(rt.storages[sa2])
        rt.eq_neighborhood_cost(rt.storages[sb1])
        rt.eq_neighborhood_cost(rt.storages[sb2])
        assert sb1 in rt._eq_cache and sb2 in rt._eq_cache
        rt.get(a2)  # remat in chain 2 only
        assert sb1 in rt._eq_cache
        assert sb2 not in rt._eq_cache

    def test_cached_costs_match_scratch_recomputation(self):
        """Under-invalidation detector.  The linear-scan oracle shares the
        scoped caches, so index-vs-oracle equivalence alone cannot catch a
        missed invalidation — both engines would make the same wrong
        decision.  This check recomputes every cached e*/ẽ* entry from
        scratch at every victim selection and demands bit-equality."""

        def scratch_estar(rt, s):
            # Mirrors the live-walk semantics: dead storages are pruned,
            # their cone cost charged through each member's dead_cost.
            total, seen = 0.0, set()
            stack = [d for d in s.deps if rt._is_evicted(d)]
            while stack:
                x = stack.pop()
                if x in seen:
                    continue
                seen.add(x)
                xs = rt.storages[x]
                total += xs.local_cost + xs.dead_cost
                stack.extend(d for d in xs.deps
                             if rt._is_evicted(d) and d not in seen)
            stack = [c for c in s.children if rt._is_evicted(c)]
            while stack:
                x = stack.pop()
                if x in seen:
                    continue
                seen.add(x)
                xs = rt.storages[x]
                total += xs.local_cost + xs.dead_cost
                stack.extend(c for c in xs.children
                             if rt._is_evicted(c) and c not in seen)
            return total

        def scratch_eq(rt, s):
            # Mirrors eq_neighborhood_cost's full walk: sorted neighbor
            # order (the float-summation contract of the snapshot fast
            # path), dead members included.
            roots, total = set(), 0.0
            for nsid in sorted(s.deps | s.children):
                ns = rt.storages[nsid]
                if not ns.resident and not ns.banished:
                    r = rt.uf.find(ns.uf)
                    if r not in roots:
                        roots.add(r)
                        total += rt.uf._cost[r]
            return total

        orig = EvictIndex.pick
        checked = [0]

        def checking_pick(self, exclude):
            rt = self.rt
            for sid, (val, _n) in list(rt._estar_cache.items()):
                assert val == scratch_estar(rt, rt.storages[sid]), sid
                checked[0] += 1
            if rt.uf is not None:
                for sid, val in list(rt._eq_cache.items()):
                    assert val == scratch_eq(rt, rt.storages[sid]), sid
                    checked[0] += 1
            return orig(self, exclude)

        EvictIndex.pick = checking_pick
        try:
            for log, h in ((graphs.mlp(depth=8), "h_dtr"),
                           (graphs.random_dag(40, seed=3), "h_dtr"),
                           (graphs.mlp(depth=8), "h_dtr_eq")):
                peak, _ = simulator.measure_baseline(log)
                for dealloc in ("eager", "banish"):
                    simulator.simulate(log, h, budget=0.5 * peak,
                                       dealloc=dealloc)
        finally:
            EvictIndex.pick = orig
        assert checked[0] > 0

    def test_invalidator_counts(self):
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_dtr"))
        assert isinstance(rt._invalidator, ScopedInvalidator)
        c = rt.constant(1)
        rt.call("a", 1.0, [c], [10])
        assert rt._invalidator.invalidations > 0


# ---------------------------------------------------------------------------
# Incremental component sums + exact split invalidation
# ---------------------------------------------------------------------------

def brute_component_sum(rt, s):
    """Re-derive s's component sum from member costs (ground truth)."""
    root = rt.uf.find(s.uf)
    return sum(x.local_cost for x in rt.storages.values()
               if x.uf_joined and rt.uf.find(x.uf) == root)


class TestIncrementalComponentSums:
    def _chain_rt(self, n=6):
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_dtr_eq"))
        c = rt.constant(1)
        tids = [c]
        for i in range(n):
            (t,) = rt.call(f"op{i}", float(i + 1), [tids[-1]], [10])
            tids.append(t)
        return rt, tids

    def test_union_and_add_cost_track_members(self):
        """Per-root sums maintained on union/add_cost equal a brute-force
        re-walk over the members."""
        rt, tids = self._chain_rt()
        sids = [rt.tensors[t].sid for t in tids[1:]]
        for t in tids[1:4]:
            rt._evict(rt.storages[rt.tensors[t].sid])
        s1 = rt.storages[sids[0]]
        assert rt.uf._cost[rt.uf.find(s1.uf)] == brute_component_sum(rt, s1)
        # Alias registration on an evicted member grows the sum in place.
        rt.call("view", 2.5, [tids[5]], [0], aliases=[tids[2]])
        assert rt.uf._cost[rt.uf.find(s1.uf)] == brute_component_sum(rt, s1)

    def test_split_approx_subtracts_member(self):
        rt, tids = self._chain_rt()
        # Evict only tids[2], tids[3]: tids[1] stays resident so the remat
        # of tids[2] below detaches exactly one member.
        for t in (tids[2], tids[3]):
            rt._evict(rt.storages[rt.tensors[t].sid])
        s2 = rt.storages[rt.tensors[tids[2]].sid]
        s3 = rt.storages[rt.tensors[tids[3]].sid]
        before = rt.uf._cost[rt.uf.find(s2.uf)]
        assert before == pytest.approx(s2.local_cost + s3.local_cost)
        rt.get(tids[2])  # remat: split_approx detaches tids[2]'s storage
        assert not s2.uf_joined
        after = rt.uf._cost[rt.uf.find(s3.uf)]
        assert after == pytest.approx(before - s2.local_cost)
        assert after == brute_component_sum(rt, s3)

    def test_eq_keys_exact_after_split_remerge(self):
        """Satellite regression: evict, remat, re-evict a shared-neighbor
        chain — every cached eq key must equal a from-scratch recompute
        (stale entries for the detached storage must be dropped on splits,
        not just on merges)."""
        rt, tids = self._chain_rt()
        sids = [rt.tensors[t].sid for t in tids]
        # Evict interior b, c of chain a-b-c-d (a, d stay resident).
        for t in (tids[2], tids[3]):
            rt._evict(rt.storages[rt.tensors[t].sid])

        def assert_eq_cache_exact():
            for sid, val in list(rt._eq_cache.items()):
                s = rt.storages[sid]
                roots, want = set(), 0.0
                for nsid in sorted(s.deps | s.children):
                    ns = rt.storages[nsid]
                    if not ns.resident and not ns.banished:
                        r = rt.uf.find(ns.uf)
                        if r not in roots:
                            roots.add(r)
                            want += rt.uf._cost[r]
                assert val == want, sid
                assert want == pytest.approx(
                    brute_component_sum_of_neighbors(rt, s))

        def brute_component_sum_of_neighbors(rt, s):
            roots, total = set(), 0.0
            for nsid in sorted(s.deps | s.children):
                ns = rt.storages[nsid]
                if not ns.resident and not ns.banished:
                    r = rt.uf.find(ns.uf)
                    if r not in roots:
                        roots.add(r)
                        total += sum(
                            x.local_cost for x in rt.storages.values()
                            if x.uf_joined and rt.uf.find(x.uf) == r)
            return total

        # Warm consumer caches on both shared neighbors.
        for t in (tids[1], tids[4]):
            rt.eq_neighborhood_cost(rt.storages[rt.tensors[t].sid])
        assert_eq_cache_exact()
        rt.get(tids[2])                      # remat: split
        for t in (tids[1], tids[4]):
            rt.eq_neighborhood_cost(rt.storages[rt.tensors[t].sid])
        assert_eq_cache_exact()
        rt._evict(rt.storages[rt.tensors[tids[2]].sid])  # re-evict: merge
        for t in (tids[1], tids[4]):
            rt.eq_neighborhood_cost(rt.storages[rt.tensors[t].sid])
        assert_eq_cache_exact()

    def test_snapshot_fast_path_used(self):
        """A sum-only invalidation rebuilds the eq key from the adjacency
        snapshot (no re-walk: subscription count stays flat)."""
        rt, tids = self._chain_rt()
        rt._evict(rt.storages[rt.tensors[tids[2]].sid])
        s1 = rt.storages[rt.tensors[tids[1]].sid]
        rt.eq_neighborhood_cost(s1)
        assert s1.sid in rt._eq_adj
        subs_before = rt._invalidator.subscribes
        # Evict a storage two hops away: merges tids[2]'s component ->
        # sum-only invalidation for s1 (adjacency unchanged).
        rt._evict(rt.storages[rt.tensors[tids[3]].sid])
        assert s1.sid not in rt._eq_cache       # value dropped
        assert s1.sid in rt._eq_adj             # snapshot survived
        val = rt.eq_neighborhood_cost(s1)       # fast-path rebuild
        assert rt._invalidator.subscribes == subs_before  # no re-walk
        assert val == pytest.approx(brute_component_sum(
            rt, rt.storages[rt.tensors[tids[2]].sid]))

    def test_phantom_rebuild_restores_exact_partition(self):
        """Amortized exact splits: once phantoms outnumber live members,
        the true components are re-derived (no unbounded mega-component)."""
        rt, tids = self._chain_rt(8)
        # Evict the whole interior chain -> one big component.
        for t in tids[1:8]:
            rt._evict(rt.storages[rt.tensors[t].sid])
        # Remat most interior members: phantoms pile up.
        for t in tids[2:7]:
            rt.get(t)
        s1 = rt.storages[rt.tensors[tids[1]].sid]
        # After the rebuild the surviving component holds exactly the
        # still-evicted members connected to tids[1]'s storage.
        assert rt.uf._cost[rt.uf.find(s1.uf)] == brute_component_sum(rt, s1)


# ---------------------------------------------------------------------------
# Dead-subgraph pruning
# ---------------------------------------------------------------------------

class TestDeadSubgraphPruning:
    def _rt(self, heuristic="h_dtr", **kw):
        return DTRRuntime(budget=float("inf"), heuristic=by_name(heuristic),
                          dealloc="eager", **kw)

    def test_release_cascades_death_backward(self):
        """A fully-released subgraph dies child-first back to the frontier."""
        rt = self._rt()
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 2.0, [a], [10])
        (d,) = rt.call("d", 4.0, [b], [10])
        sa, sb, sd = (rt.tensors[t].sid for t in (a, b, d))
        rt.release(d)
        assert rt.storages[sd].dead
        # b still holds an external ref -> alive; a alive through b.
        assert not rt.storages[sb].dead and not rt.storages[sa].dead
        rt.release(b)
        assert rt.storages[sb].dead
        rt.release(a)
        assert rt.storages[sa].dead

    def test_live_child_keeps_parent_alive(self):
        rt = self._rt()
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 2.0, [a], [10])
        rt.release(a)
        assert not rt.storages[rt.tensors[a].sid].dead
        rt.release(b)   # now the whole chain is unreferenced
        assert rt.storages[rt.tensors[a].sid].dead

    def test_dead_pruned_from_estar_walk_with_cone_attached(self):
        """e* walks skip dead members; the cone's cost is charged through
        the live frontier's dead_cost instead."""
        rt = self._rt()
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 2.0, [a], [10])   # b: child of a, will die
        (k,) = rt.call("k", 8.0, [a], [10])   # keeps a alive
        sa = rt.tensors[a].sid
        rt.release(b)                          # leaf dies -> eager evict
        sb = rt.tensors[b].sid
        assert rt.storages[sb].dead and not rt.storages[sb].resident
        # a (live, resident) carries the cone weight ...
        assert rt.storages[sa].dead_cost == pytest.approx(2.0)
        # ... and the e* walk from k's storage never visits the dead b,
        # but still charges it when a is evicted.
        rt._evict(rt.storages[sa])
        sk = rt.tensors[k].sid
        cost = rt.evicted_neighborhood_cost(rt.storages[sk])
        assert cost == pytest.approx(1.0 + 2.0)  # a.local + cone(b)
        assert sb not in {x for x in rt._invalidator._subs.get(
            rt._invalidator._uf.find(rt._invalidator._node.get(sa, 0)),
            set())}

    def test_dead_never_subscribes(self):
        """Dead evictions register no subscriptions and fire no component
        merges — subscriber work stays bounded on retire-heavy traces."""
        rt = self._rt()
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 2.0, [a], [10])
        rt.release(b)
        rt.release(a)
        subs = rt._invalidator.subscribes
        # Scoring any candidate must not walk (or subscribe through) the
        # dead chain.
        rt.constant(1)
        assert rt._invalidator.subscribes == subs

    def test_dead_members_stay_in_eq_components(self):
        """ẽ* keeps dead members as cost ballast (h_dtr_eq accounting)."""
        rt = self._rt("h_dtr_eq")
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 2.0, [a], [10])
        (k,) = rt.call("k", 8.0, [a], [10])
        rt.release(b)                          # dies, evicted, joins
        sb = rt.tensors[b].sid
        assert rt.storages[sb].dead and rt.storages[sb].uf_joined
        rt._evict(rt.storages[rt.tensors[a].sid])
        sk = rt.tensors[k].sid
        # Component of a contains dead b: 1.0 + 2.0.
        assert rt.eq_neighborhood_cost(
            rt.storages[sk]) == pytest.approx(3.0)

    def test_addref_revives_dead_chain(self):
        rt = self._rt()
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 2.0, [a], [10])
        rt.release(b)
        rt.release(a)
        sa, sb = rt.tensors[a].sid, rt.tensors[b].sid
        assert rt.storages[sa].dead and rt.storages[sb].dead
        rt.addref(b)
        assert not rt.storages[sb].dead
        assert not rt.storages[sa].dead        # ancestors revive too

    def test_dead_children_do_not_block_banish(self):
        """A dead evicted child never rematerializes, so it must not leave
        its parent pending-banish forever."""
        rt = DTRRuntime(budget=float("inf"), heuristic=by_name("h_dtr"),
                        dealloc="banish")
        c = rt.constant(1)
        (a,) = rt.call("a", 1.0, [c], [10])
        (b,) = rt.call("b", 2.0, [a], [10])
        sa, sb = rt.tensors[a].sid, rt.tensors[b].sid
        # Kill b while evicted: release drops it to dead (banish policy
        # banishes it instead unless blocked; force the evicted-dead shape
        # via eager-style evict first).
        rt.storages[sb].locks += 1             # block banish of b
        rt.release(b)
        rt.storages[sb].locks -= 1
        rt.release(a)
        assert rt.storages[sa].banished
        assert sa not in rt._pending_banish

    def test_oracle_equivalence_with_deaths(self):
        """Scan and index engines agree bit-exactly on a log whose replay
        produces dead subgraphs (eager releases of leaf outputs)."""
        for heuristic in ("h_dtr", "h_dtr_eq", "h_msps", "h_estar"):
            log = graphs.random_dag(60, seed=11)
            peak, _ = simulator.measure_baseline(log)
            a, b = both(log, heuristic, 0.5 * peak, dealloc="eager")
            assert_parity(a, b, f"dead/{heuristic}")


# ---------------------------------------------------------------------------
# window_cost / score-cache sharing
# ---------------------------------------------------------------------------

class TestWindowCostSharing:
    def test_window_cost_uses_index_memo(self):
        rt = DTRRuntime(budget=1000, heuristic=by_name("h_lru"))
        assert rt.index is not None
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [20])
        (b,) = rt.call("g", 1.0, [c], [20])
        sa = rt.storages[rt.tensors[a].sid]
        sb = rt.storages[rt.tensors[b].sid]
        before = rt.meta_accesses
        c1 = window_cost(rt, rt.heuristic, [sa, sb])
        assert rt.meta_accesses == before + 2    # two fresh evaluations
        c2 = window_cost(rt, rt.heuristic, [sa, sb])
        assert rt.meta_accesses == before + 2    # memo hits: no new accesses
        assert c1 == c2

    def test_window_cost_matches_pick_accounting(self):
        """A storage scored by the window planner and then verified by
        victim selection at the same instant costs one access total."""
        rt = DTRRuntime(budget=1000, heuristic=by_name("h_lru"))
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [20])
        sa = rt.storages[rt.tensors[a].sid]
        before = rt.meta_accesses
        sc1 = window_cost(rt, rt.heuristic, [sa])
        sc2 = rt.index.cached_score(sa)
        assert sc1 == sc2
        assert rt.meta_accesses == before + 1

    def test_explicit_cache_dict_still_honored(self):
        rt = DTRRuntime(budget=1000, heuristic=by_name("h_lru"))
        c = rt.constant(10)
        (a,) = rt.call("f", 1.0, [c], [20])
        sa = rt.storages[rt.tensors[a].sid]
        cache = {}
        c1 = window_cost(rt, rt.heuristic, [sa], cache=cache)
        assert cache[sa.sid] == c1


# ---------------------------------------------------------------------------
# Parallel sweep driver
# ---------------------------------------------------------------------------

class TestSweepParallel:
    FR = [0.9, 0.6]

    def _flat(self, sweeps):
        out = []
        for sw in sweeps:
            for r in sw.runs:
                out.append((sw.log_name, sw.heuristic, r.budget, r.ok,
                            r.compute, r.evictions, r.peak_memory))
        return out

    def test_matches_serial_sweep(self):
        logs = [graphs.mlp(depth=6), graphs.linear_network(40)]
        hs = ["h_dtr_eq", "h_lru"]
        serial = [simulator.sweep(log, h, self.FR) for log in logs for h in hs]
        par = simulator.sweep_parallel(logs, hs, self.FR, processes=2)
        assert self._flat(par) == self._flat(serial)

    def test_pool_after_jax_does_not_fork(self):
        """A sweep started by a process that has run JAX spawns its workers:
        forking a multithreaded JAX process can deadlock the child."""
        import jax.numpy as jnp
        jnp.ones(4).block_until_ready()
        logs = [graphs.mlp(depth=4)]
        hs = ["h_dtr_eq", "h_lru"]
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            par = simulator.sweep_parallel(logs, hs, self.FR, processes=2)
        serial = [simulator.sweep(logs[0], h, self.FR) for h in hs]
        assert self._flat(par) == self._flat(serial)
        assert not [w for w in caught if "os.fork()" in str(w.message)]

    def test_serial_fallback_path(self):
        logs = [graphs.mlp(depth=4)]
        par = simulator.sweep_parallel(logs, ["h_lru"], self.FR, processes=0)
        serial = [simulator.sweep(logs[0], "h_lru", self.FR)]
        assert self._flat(par) == self._flat(serial)

    def test_single_log_and_heuristic_convenience(self):
        log = graphs.mlp(depth=4)
        out = simulator.sweep_parallel(log, "h_lru", [0.8], processes=0)
        assert len(out) == 1 and out[0].heuristic == "h_lru"
        assert len(out[0].runs) == 1
