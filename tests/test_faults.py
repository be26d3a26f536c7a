"""Tests for ``repro.faults``: chaos schedules, the recovery ladder, the
serve admission controller, and the robustness satellites (partial-progress
results, worker-death sweeps, simultaneous tier exhaustion).

The differential invariants:

* a disabled/absent schedule is **bit-exact** with the pre-faults engine
  (victims, counters, no events);
* a pinned schedule is **deterministic**: identical victims and event
  streams across runs and across the scan/index engines;
* alloc faults alone can never kill a run (the ladder absorbs them);
* a recovered *eager* run computes the same numerics as a fault-free one.
"""
import os

import pytest

from repro.core import graphs
from repro.core.simulator import (RunResult, measure_baseline,
                                  resolve_budget, simulate, sweep_parallel)
from repro.faults import FaultConfig, FaultSchedule, RecoveryConfig
from repro.launch.admission import (ADMIT, REJECT, WAIT,
                                    AdmissionController, Ticket)
from repro.offload import OffloadConfig
from repro.trace.replay import PARITY_FIELDS, run_to_dict, run_trace

from tests.test_trace_golden import load_trace


# ---------------------------------------------------------------------------
# Schedule determinism
# ---------------------------------------------------------------------------

class TestSchedule:
    CFG = FaultConfig(seed=7, alloc_rate=0.3, transfer_rate=0.3,
                      spike_rate=0.2, prefetch_rate=0.4, cost_noise=0.2,
                      budget_shrink=0.4, budget_period=16)

    def test_draws_are_pure_functions_of_seed_kind_index(self):
        a, b = FaultSchedule(self.CFG), FaultSchedule(self.CFG)
        assert ([a.alloc_fault() for _ in range(64)]
                == [b.alloc_fault() for _ in range(64)])
        assert ([a.prefetch_lost() for _ in range(64)]
                == [b.prefetch_lost() for _ in range(64)])
        assert ([a.transfer_plan("h2d", 100, 1.0) for _ in range(32)]
                == [b.transfer_plan("h2d", 100, 1.0) for _ in range(32)])

    def test_kinds_do_not_interleave(self):
        # Drawing kind B between draws of kind A must not shift A's
        # stream: per-kind counters, not a shared RNG.
        a, b = FaultSchedule(self.CFG), FaultSchedule(self.CFG)
        seq_a = [a.alloc_fault() for _ in range(32)]
        seq_b = []
        for _ in range(32):
            seq_b.append(b.alloc_fault())
            b.prefetch_lost()
            b.transfer_plan("d2h", 10, 1.0)
        assert seq_a == seq_b

    def test_channels_draw_independently(self):
        a, b = FaultSchedule(self.CFG), FaultSchedule(self.CFG)
        h2d = [a.transfer_plan("h2d", 10, 1.0) for _ in range(16)]
        for _ in range(16):
            b.transfer_plan("d2h", 10, 1.0)
        assert h2d == [b.transfer_plan("h2d", 10, 1.0) for _ in range(16)]

    def test_cost_factor_keyed_by_op_identity(self):
        s = FaultSchedule(self.CFG)
        f1 = s.cost_factor(3)
        s.cost_factor(11)
        assert s.cost_factor(3) == f1          # cached, consistent
        assert FaultSchedule(self.CFG).cost_factor(3) == f1
        assert s.cost_factor(4) != f1          # per-op, not global

    def test_transfer_retry_backoff_math(self):
        cfg = FaultConfig(seed=0, transfer_rate=1.0, spike_rate=1.0,
                          spike_mult=4.0, max_transfer_retries=3,
                          backoff_base=0.5, backoff_cap=1.0)
        extra, retries, mult = FaultSchedule(cfg).transfer_plan(
            "h2d", 100, 2.0)
        assert mult == 4.0
        assert retries == 3                    # rate 1.0 -> always the cap
        dur = 2.0 * 4.0
        want = (dur + 0.5 * dur) + (dur + 1.0 * dur) + (dur + 1.0 * dur)
        assert extra == pytest.approx(want)

    def test_budget_square_wave(self):
        cfg = FaultConfig(budget_shrink=0.4, budget_period=10,
                          budget_duty=0.3)
        s = FaultSchedule(cfg)
        assert all(s.budget_factor(i) == 1.0 for i in range(10))  # grace
        assert s.budget_factor(10) == pytest.approx(0.6)
        assert s.budget_factor(12) == pytest.approx(0.6)          # duty=3 ops
        assert s.budget_factor(13) == 1.0
        assert s.budget_factor(20) == pytest.approx(0.6)

    def test_disabled_config_refuses_schedule(self):
        assert not FaultConfig().enabled
        with pytest.raises(AssertionError):
            FaultSchedule(FaultConfig())


# ---------------------------------------------------------------------------
# Differential bit-exactness + pinned-schedule determinism
# ---------------------------------------------------------------------------

class TestDifferential:
    @pytest.mark.parametrize("name,frac", [("treelstm", 0.8),
                                           ("random_dag", 0.5),
                                           ("eager_mlp", 0.8)])
    def test_zero_rate_is_bit_exact(self, name, frac):
        log = load_trace(name)
        peak, _ = measure_baseline(log)
        budget = resolve_budget(frac, peak, log.pinned_bytes(),
                                "activation")
        plain, vic_p = run_trace(log, "h_dtr_eq", budget, thrash_factor=3.0)
        zero, vic_z = run_trace(log, "h_dtr_eq", budget, thrash_factor=3.0,
                                faults=FaultConfig(seed=9))
        assert vic_p == vic_z
        for f in PARITY_FIELDS:
            assert getattr(plain, f) == getattr(zero, f), f
        assert zero.degradations == 0 and zero.events == []

    def test_pinned_schedule_deterministic_across_runs_and_engines(self):
        log = load_trace("treelstm")
        peak, cost = measure_baseline(log)
        pinned = log.pinned_bytes()
        budget = resolve_budget(0.6, peak, pinned, "activation")
        bw = 2 * peak / cost
        off = OffloadConfig(host_budget=peak - pinned, h2d_bandwidth=bw,
                            d2h_bandwidth=bw)
        cfg = FaultConfig(seed=11, alloc_rate=0.05, transfer_rate=0.05,
                          spike_rate=0.05, prefetch_rate=0.2,
                          cost_noise=0.05, budget_shrink=0.3,
                          budget_period=64)
        runs = [run_trace(log, "h_dtr_eq", budget, thrash_factor=10.0,
                          offload=off, faults=cfg,
                          recovery=RecoveryConfig(), index=idx)
                for idx in (True, True, False)]
        (r1, v1), (r2, v2), (r3, v3) = runs
        assert v1 == v2 == v3
        assert r1.events == r2.events == r3.events
        for f in PARITY_FIELDS:
            assert getattr(r1, f) == getattr(r3, f), f

    def test_event_schema(self):
        log = load_trace("random_dag")
        peak, _ = measure_baseline(log)
        budget = resolve_budget(0.5, peak, log.pinned_bytes(), "activation")
        r = simulate(log, "h_dtr_eq", budget, thrash_factor=10.0,
                     faults=FaultConfig(seed=2, alloc_rate=0.2))
        assert r.degradations > 0
        for ev in r.events:
            assert {"kind", "op", "clock"} <= set(ev)


# ---------------------------------------------------------------------------
# Recovery ladder
# ---------------------------------------------------------------------------

class TestRecovery:
    def test_alloc_faults_alone_never_kill(self):
        # Even an absurd 50% admission-failure rate must be absorbed by
        # the headroom-eviction recovery: the fault is transient by
        # construction, so the retry always proceeds.
        log = load_trace("treelstm")
        peak, _ = measure_baseline(log)
        budget = resolve_budget(0.6, peak, log.pinned_bytes(), "activation")
        base = simulate(log, "h_dtr_eq", budget, thrash_factor=10.0)
        r = simulate(log, "h_dtr_eq", budget, thrash_factor=10.0,
                     faults=FaultConfig(seed=1, alloc_rate=0.5))
        assert base.ok and r.ok
        assert r.degradations > 0
        assert any(ev["kind"] == "alloc_fault" for ev in r.events)

    def test_alloc_fault_pool_mode_recovers_via_compaction(self):
        log = graphs.random_dag(80, seed=2)
        peak, _ = measure_baseline(log)
        r = simulate(log, "h_dtr", 0.6 * peak, thrash_factor=20.0,
                     alloc_mode="pool",
                     faults=FaultConfig(seed=4, alloc_rate=0.3))
        assert r.ok
        assert any(ev["kind"] == "alloc_fault" for ev in r.events)

    def test_budget_squeeze_emits_shrink_and_restore(self):
        log = graphs.linear_network(64)
        peak, _ = measure_baseline(log)
        r = simulate(log, "h_dtr", 0.8 * peak, thrash_factor=20.0,
                     faults=FaultConfig(budget_shrink=0.3,
                                        budget_period=16))
        assert r.ok
        shr = [ev for ev in r.events if ev["kind"] == "budget_shrink"]
        res = [ev for ev in r.events if ev["kind"] == "budget_restore"]
        assert shr and res
        assert all(ev["factor"] == pytest.approx(0.7) for ev in shr)
        assert all(ev["factor"] == 1.0 for ev in res)

    def test_thrash_guard_escalates_instead_of_dying(self):
        # h_lru grinds eager_mlp at thrash_factor 2 (golden corpus:
        # slowdown 2.7x); the guard must switch to h_dtr mid-run and
        # finish where the unguarded run hits the ThrashError cliff.
        log = load_trace("eager_mlp")
        peak, _ = measure_baseline(log)
        budget = resolve_budget(0.8, peak, log.pinned_bytes(), "activation")
        dead = run_trace(log, "h_lru", budget, thrash_factor=2.0)[0]
        assert not dead.ok and dead.error_kind == "thrash"
        rc = RecoveryConfig(thrash_window_ops=8, thrash_ratio=1.5,
                            escalation_chain=("h_dtr",))
        saved = run_trace(log, "h_lru", budget, thrash_factor=2.0,
                          recovery=rc)[0]
        assert saved.ok
        esc = [ev for ev in saved.events
               if ev["kind"] == "heuristic_escalation"]
        assert esc and esc[0]["reason"] == "thrash_guard"
        assert esc[0]["to"] == "h_dtr"

    def test_forced_offload_rung_bypasses_two_choice_key(self):
        # Unit test of the ladder rung itself: with a host tier attached
        # but priced out by the two-choice key (tiny bandwidth, so
        # ordinary pressure always evicts), the rung must still park the
        # minimum-transfer-key evictable storage on the host — freeing
        # device bytes without creating remat debt — and log the event.
        from repro.core.graph import replay
        from repro.core.heuristics import by_name
        from repro.core.runtime import DTRRuntime
        from repro.offload import OffloadEngine, wrap_heuristic
        log = graphs.linear_network(8)
        peak, cost = measure_baseline(log)
        bw = 0.01 * peak / cost          # transfers ~never win the key
        eng = OffloadEngine(OffloadConfig(host_budget=peak,
                                          h2d_bandwidth=bw,
                                          d2h_bandwidth=bw))
        h = wrap_heuristic(by_name("h_dtr", 0), eng)
        rt = DTRRuntime(budget=2 * peak, heuristic=h, offload=eng,
                        dealloc="ignore", recovery=RecoveryConfig())
        replay(log, rt)                  # generous budget: no pressure
        assert rt.offloads == 0
        pool = [s for s in rt.storages.values()
                if s.evictable() and s.size > 0]
        assert pool
        want = min(pool, key=lambda s: (eng.transfer_key(s), s.sid))
        assert rt._forced_offload(set())
        assert rt.offloads == 1 and rt.degradations == 1
        ev = [e for e in rt.events if e["kind"] == "forced_offload"]
        assert len(ev) == 1 and ev[0]["sid"] == want.sid
        assert not rt.storages[want.sid].resident
        # Excluding that victim forces the next-cheapest choice.
        assert rt._forced_offload({want.sid})
        ev2 = [e for e in rt.events if e["kind"] == "forced_offload"]
        assert ev2[-1]["sid"] != want.sid

    def test_recovery_none_is_default_and_inert(self):
        log = graphs.linear_network(24)
        peak, _ = measure_baseline(log)
        a = simulate(log, "h_dtr", 0.3 * peak, thrash_factor=50.0)
        b = simulate(log, "h_dtr", 0.3 * peak, thrash_factor=50.0,
                     recovery=None)
        assert run_to_dict(a) == run_to_dict(b)
        assert a.events == [] and a.degradations == 0


# ---------------------------------------------------------------------------
# Satellites: partial progress, error kinds, enriched diagnostics
# ---------------------------------------------------------------------------

class TestFailureReporting:
    def test_failed_run_records_partial_progress(self):
        log = load_trace("eager_mlp")
        peak, _ = measure_baseline(log)
        budget = resolve_budget(0.8, peak, log.pinned_bytes(), "activation")
        r = run_trace(log, "h_lru", budget, thrash_factor=1.5)[0]
        assert not r.ok and r.error_kind == "thrash"
        assert r.ops_executed > 0
        assert 0.0 < r.slowdown < float("inf")
        assert 0.0 < r.overhead < float("inf")
        d = run_to_dict(r)
        assert d["slowdown"] == r.slowdown     # finite -> survives to JSON

    def test_oom_error_kind_and_diagnostics(self):
        log = graphs.linear_network(16)
        peak, _ = measure_baseline(log)
        r = simulate(log, "h_dtr", 0.05 * peak, thrash_factor=50.0)
        assert not r.ok and r.error_kind == "oom"
        assert "resident=" in r.error and "pinned=" in r.error
        assert "top remats:" in r.error

    def test_thrash_error_diagnostics(self):
        log = load_trace("eager_mlp")
        peak, _ = measure_baseline(log)
        budget = resolve_budget(0.8, peak, log.pinned_bytes(), "activation")
        r = run_trace(log, "h_lru", budget, thrash_factor=1.5)[0]
        assert "thrash limit" in r.error and "degradations=" in r.error

    def test_faulted_failure_classified_as_fault(self):
        # A run that dies *with injected faults fired* is "unlucky", not
        # infeasible: squeeze the budget hard enough to kill a cell that
        # is feasible fault-free.
        log = load_trace("eager_mlp")
        peak, _ = measure_baseline(log)
        budget = resolve_budget(0.8, peak, log.pinned_bytes(), "activation")
        r = simulate(log, "h_lru", budget, thrash_factor=2.0,
                     faults=FaultConfig(seed=3, cost_noise=0.8),
                     recovery=RecoveryConfig(thrash_guard=False))
        if r.ok:
            pytest.skip("noise draw too gentle to kill the cell")
        assert r.error_kind == "fault"


# ---------------------------------------------------------------------------
# Satellite: worker death mid-sweep
# ---------------------------------------------------------------------------

def _lru_killer(payload):
    """Replacement _simulate_task: h_lru cells kill their worker."""
    if payload[2] == "h_lru":
        os._exit(17)
    from repro.core import simulator
    return _REAL_TASK(payload)


from repro.core.simulator import _simulate_task as _REAL_TASK  # noqa: E402


class TestWorkerDeath:
    def test_dead_worker_fails_only_its_cell(self, monkeypatch):
        # The pool looks the task up at submit time, so the patched killer
        # is pickled by name and imported by each spawned worker.
        from repro.core import simulator
        monkeypatch.setattr(simulator, "_simulate_task", _lru_killer)
        log = graphs.linear_network(24)
        sweeps = simulator.sweep_parallel(
            log, ["h_dtr", "h_lru", "h_size"], [0.9, 0.5],
            processes=2, thrash_factor=50.0)
        by_h = {sw.heuristic: sw for sw in sweeps}
        assert all(r.ok for r in by_h["h_dtr"].runs)
        assert all(r.ok for r in by_h["h_size"].runs)
        for r in by_h["h_lru"].runs:
            assert not r.ok and r.error_kind == "worker"
            assert "died" in r.error
        # The surviving cells match an undisturbed serial sweep.
        serial = simulator.sweep_parallel(
            log, ["h_dtr"], [0.9, 0.5], processes=0, thrash_factor=50.0)
        assert ([run_to_dict(r) for r in by_h["h_dtr"].runs]
                == [run_to_dict(r) for r in serial[0].runs])


# ---------------------------------------------------------------------------
# Satellite: simultaneous device + host exhaustion (pool+host)
# ---------------------------------------------------------------------------

class TestSimultaneousExhaustion:
    def test_full_host_demotes_offload_to_plain_eviction(self):
        # Host tier sized for a handful of storages: once it fills, every
        # would-be offload deterministically becomes a plain eviction
        # (documented in OffloadEngine.wants_offload) — no evict-from-host
        # path, and the run either completes as pure DTR or dies with a
        # controlled OOM.
        log = graphs.random_dag(60, seed=3)
        peak, cost = measure_baseline(log)
        bw = 50.0 * peak / cost          # transfers always win the key
        sizes = sorted(
            {i.size for i in log.instrs if hasattr(i, "size")
             and getattr(i, "size", 0) > 0})
        host_cap = 3 * sizes[-1]         # room for ~3 largest storages
        cfg = OffloadConfig(host_budget=host_cap, h2d_bandwidth=bw,
                            d2h_bandwidth=bw)
        r1, r2, r3 = [
            simulate(log, "h_dtr_eq", 0.4 * peak, thrash_factor=50.0,
                     alloc_mode="pool+host", offload=cfg, index=idx)
            for idx in (True, True, False)]
        # Deterministic across runs AND engines (the documented path).
        for f in PARITY_FIELDS:
            assert getattr(r1, f) == getattr(r2, f), f
            assert getattr(r1, f) == getattr(r3, f), f
        # The host filled and pressure continued: evictions happened on
        # top of offloads even though transfers always price cheaper.
        assert r1.offloads > 0
        assert r1.evictions > 0
        if not r1.ok:
            assert r1.error_kind == "oom" and "resident=" in r1.error

    def test_exhaustion_with_nothing_evictable_is_controlled_oom(self):
        # Tiny device budget + tiny host: the first oversized allocation
        # finds both tiers exhausted and must raise the enriched OOM, not
        # hang or corrupt state.
        log = graphs.linear_network(16)
        peak, cost = measure_baseline(log)
        bw = 50.0 * peak / cost
        cfg = OffloadConfig(host_budget=0.02 * peak, h2d_bandwidth=bw,
                            d2h_bandwidth=bw)
        r = simulate(log, "h_dtr_eq", 0.05 * peak, thrash_factor=50.0,
                     alloc_mode="pool+host", offload=cfg)
        assert not r.ok and r.error_kind == "oom"
        assert "resident=" in r.error


# ---------------------------------------------------------------------------
# Eager-mode numerics under faults
# ---------------------------------------------------------------------------

class TestEagerNumerics:
    def _run_chain(self, faults=None, recovery=None):
        jnp = pytest.importorskip("jax.numpy")
        import numpy as np
        from repro.eager import DTRContext, op
        ctx = DTRContext(budget_bytes=3000, heuristic="h_dtr_eq",
                         use_wallclock_cost=False, faults=faults,
                         recovery=recovery)
        mul = op(ctx, "mul", jnp.multiply)
        add = op(ctx, "add", jnp.add)
        x = ctx.wrap(np.arange(64, dtype=np.float32).reshape(8, 8))
        ys = []
        h = x
        for i in range(12):
            h = add(mul(h, x), x)
            ys.append(h)
        outs = [np.asarray(y.value) for y in ys[-3:]]
        return outs, ctx.rt

    def test_recovered_run_matches_fault_free_numerics(self):
        import numpy as np
        clean, rt_clean = self._run_chain()
        cfg = FaultConfig(seed=5, alloc_rate=0.4, cost_noise=0.3)
        faulted, rt_f = self._run_chain(faults=cfg)
        assert rt_clean.evictions > 0          # pressure actually existed
        assert rt_f.faults.injected > 0        # faults actually fired
        for a, b in zip(clean, faulted):
            assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Eager-mode transfer faults: faulted H2D/D2H draws through the live
# executor must produce the same structured event stream the simulator
# emits replaying the recorded schedule
# ---------------------------------------------------------------------------

class TestEagerTransferFaults:
    """The eager executor and the trace simulator share one DTRRuntime, so
    a recorded eager program replayed under the same OffloadConfig /
    FaultConfig must take bit-identical transfer decisions — including the
    fault draws, which are keyed to the transfer sequence.  Every release
    the program performs is recorded, and the final fetch mirrors replay's
    output condition, so the two engines see identical pressure end to end.
    """

    BUDGET = 3000.0

    def _cfgs(self, faults):
        off = OffloadConfig(host_budget=float(1 << 20),
                            h2d_bandwidth=1024.0, d2h_bandwidth=1024.0,
                            policy="offload")
        f = FaultConfig(seed=21, transfer_rate=0.4, spike_rate=0.4) \
            if faults else None
        r = RecoveryConfig() if faults else None
        return off, f, r

    def _run_eager(self, faults=True):
        jnp = pytest.importorskip("jax.numpy")
        import numpy as np
        from repro.eager import DTRContext, op
        from repro.trace import TraceRecorder
        off, f, r = self._cfgs(faults)
        rec = TraceRecorder("eager_fault_chain")
        ctx = DTRContext(budget_bytes=self.BUDGET, heuristic="h_dtr_eq",
                         use_wallclock_cost=False, offload=off,
                         faults=f, recovery=r, recorder=rec)
        mul = op(ctx, "mul", jnp.multiply)
        add = op(ctx, "add", jnp.add)
        x = ctx.wrap(np.arange(64, dtype=np.float32).reshape(8, 8))
        h = x
        ys = []
        for _ in range(12):
            m = mul(h, x)
            h = add(m, x)
            ys.append((m, h))
        # Keeping every intermediate drives the working set past the
        # budget (pure-offload policy: victims go to host, not dropped);
        # the late use of iteration 0's output then fetches a
        # long-offloaded tensor back through the faulted h2d channel.
        h = add(h, ys[0][1])
        for m, y in ys:
            m.release()
            y.release()
        out = np.asarray(h.value)
        h.release()
        return ctx.rt, rec.finish(), out

    def test_eager_transfer_faults_match_simulator_events(self):
        rt, log, _ = self._run_eager()
        # Both channels actually drew faults through the live executor.
        assert rt.offloads > 0 and rt.fetches > 0
        kinds = {e["kind"] for e in rt.events}
        assert "transfer_spike" in kinds and "transfer_retry" in kinds
        assert {e["channel"] for e in rt.events} == {"d2h", "h2d"}
        off, f, r = self._cfgs(True)
        res, _ = run_trace(log, "h_dtr_eq", self.BUDGET,
                           offload=off, faults=f, recovery=r)
        assert res.ok
        assert res.events == rt.events          # the satellite's headline
        assert res.offloads == rt.offloads
        assert res.fetches == rt.fetches
        assert res.evictions == rt.evictions
        assert res.remat_ops == rt.remat_ops
        assert res.compute == rt.total_compute
        assert res.peak_memory == rt.peak_memory

    def test_eager_fault_schedule_is_deterministic(self):
        rt1, log1, out1 = self._run_eager()
        rt2, log2, out2 = self._run_eager()
        import numpy as np
        assert rt1.events == rt2.events
        assert log1.dumps() == log2.dumps()
        assert np.array_equal(out1, out2)

    def test_transfer_faults_never_corrupt_numerics(self):
        import numpy as np
        rt_f, _, faulted = self._run_eager(faults=True)
        rt_c, _, clean = self._run_eager(faults=False)
        assert len(rt_f.events) > 0 and len(rt_c.events) == 0
        # Same offload decisions (spikes cost time, not residency) and
        # bit-identical results.
        assert rt_f.offloads == rt_c.offloads
        assert np.array_equal(faulted, clean)


# ---------------------------------------------------------------------------
# Serve admission controller
# ---------------------------------------------------------------------------

class TestAdmission:
    def mk(self, budget=1000.0, per_tok=10.0, **kw):
        return AdmissionController(budget, per_tok, **kw)

    def test_plain_admit_within_budget(self):
        ac = self.mk()
        t = Ticket(0, prompt_len=10, gen=10)   # 200 bytes projected
        assert ac.decide(t, {}, 0) == (ADMIT, [])
        assert ac.counters()["admitted"] == 1

    def test_structurally_impossible_request_rejected(self):
        ac = self.mk(budget=100.0)
        t = Ticket(0, prompt_len=50, gen=50)   # 1000 bytes > capacity
        assert ac.decide(t, {}, 0) == (REJECT, [])
        assert ac.counters()["rejected"] == 1

    def test_preempts_cheapest_to_rematerialize(self):
        ac = self.mk(budget=450.0)
        a, b = Ticket(0, 10, 10), Ticket(1, 10, 10)    # 200 bytes each
        new = Ticket(2, 10, 10)
        # Slot 0 has replayed 15 tokens, slot 1 only 4: slot 1 is the
        # cheaper rematerialization and must be the victim.
        verdict, victims = ac.decide(new, {0: (a, 15), 1: (b, 4)}, 0)
        assert verdict == ADMIT and victims == [1]

    def test_victims_out_of_retries_are_spared(self):
        ac = self.mk(budget=450.0, max_retries=2)
        a = Ticket(0, 10, 10, retries=2)       # exhausted
        b = Ticket(1, 10, 10, retries=1)
        verdict, victims = ac.decide(Ticket(2, 10, 10),
                                     {0: (a, 1), 1: (b, 50)}, 0)
        assert verdict == ADMIT and victims == [1]   # despite higher key
        # Only exhausted tickets active and no room: nobody preemptable,
        # so the newcomer waits rather than tossing unretryable work.
        ac2 = self.mk(budget=250.0, max_retries=2)
        verdict, victims = ac2.decide(Ticket(3, 10, 10),
                                      {0: (a, 1)}, 0)
        assert verdict == WAIT and victims == []

    def test_requeue_backoff_doubles_and_caps(self):
        ac = self.mk(backoff_steps=4, backoff_cap=10)
        t = Ticket(0, 5, 5)
        ac.requeue(t, 100)
        assert (t.retries, t.eligible_step) == (1, 104)
        ac.requeue(t, 104)
        assert (t.retries, t.eligible_step) == (2, 112)
        ac.requeue(t, 112)
        assert t.eligible_step == 122          # 4*2**2=16 capped at 10
        assert ac.counters()["requeued"] == 3

    def test_backoff_blocks_until_eligible(self):
        ac = self.mk()
        t = Ticket(0, 5, 5, eligible_step=10)
        assert ac.decide(t, {}, 9) == (WAIT, [])
        assert ac.decide(t, {}, 10) == (ADMIT, [])

    def test_squeeze_makes_requests_wait_not_rejected(self):
        chaos = FaultSchedule(FaultConfig(budget_shrink=0.9,
                                          budget_period=10))
        ac = self.mk(budget=1000.0, faults=chaos)
        t = Ticket(0, 20, 20)                  # 400 bytes
        assert ac.decide(t, {}, 5) == (ADMIT, [])     # grace period
        ac2 = self.mk(budget=1000.0, faults=FaultSchedule(
            FaultConfig(budget_shrink=0.9, budget_period=10)))
        assert ac2.decide(Ticket(1, 20, 20), {}, 11) == (WAIT, [])
        assert ac2.counters()["rejected"] == 0

    def test_enforce_sheds_cheapest_until_under_budget(self):
        ac = self.mk(budget=1000.0)
        ac.kv_budget = 1000.0
        a, b, c = Ticket(0, 20, 20), Ticket(1, 20, 20), Ticket(2, 20, 20)
        active = {0: (a, 30), 1: (b, 2), 2: (c, 10)}   # 1200 used
        victims = ac.enforce(active, 0)
        assert victims == [1]                  # cheapest replay first
        ac.kv_budget = 500.0
        victims = ac.enforce(active, 0)
        assert victims == [1, 2]


# ---------------------------------------------------------------------------
# Prefetch loss -> sync-fetch fallback
# ---------------------------------------------------------------------------

class TestPrefetchLoss:
    def test_lost_prefetches_fall_back_to_sync_fetch(self):
        log = graphs.lstm(steps=24, width=8, batch=4)
        peak, cost = measure_baseline(log)
        bw = 8.0 * peak / cost
        off = OffloadConfig(host_budget=peak, h2d_bandwidth=bw,
                            d2h_bandwidth=bw, policy="offload",
                            prefetch=True)
        clean = simulate(log, "h_dtr_eq", 0.5 * peak, offload=off)
        lossy = simulate(log, "h_dtr_eq", 0.5 * peak, offload=off,
                         faults=FaultConfig(seed=1, prefetch_rate=1.0))
        assert clean.ok and lossy.ok
        assert clean.prefetch_hits > 0
        assert lossy.prefetch_hits == 0        # every prefetch was lost
        assert any(ev["kind"] == "prefetch_lost" for ev in lossy.events)
        # The accesses still happened, paying the synchronous transfer —
        # charged to the stall metric, never to recompute (pure offload
        # policy: downstream offload decisions legitimately diverge once
        # residency differs, so totals are compared within the run).
        assert lossy.fetches > 0 and lossy.stall_time > 0
        assert lossy.compute == lossy.base_compute
