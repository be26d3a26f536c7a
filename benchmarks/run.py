"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary lines (detailed per-row CSVs
are printed by each module's own main, reachable via
``python -m benchmarks.<name>``).
"""
from __future__ import annotations

import sys
import time


def _timed(name, fn, derive):
    t0 = time.perf_counter()
    rows = fn()
    us = int((time.perf_counter() - t0) * 1e6 / max(len(rows), 1))
    print(f"{name},{us},{derive(rows)}", flush=True)
    return rows


def fig2_heuristics():
    from . import fig2_heuristics as m

    def derive(rows):
        # min feasible budget fraction for h_dtr_eq vs h_lru (avg over models)
        def min_ok(h):
            per = {}
            for r in rows:
                if r["heuristic"] == h and r["ok"]:
                    per.setdefault(r["model"], []).append(r["budget"])
            vals = [min(v) for v in per.values() if v]
            return round(sum(vals) / max(len(vals), 1), 3)
        return (f"min_budget h_dtr_eq={min_ok('h_dtr_eq')} "
                f"h_lru={min_ok('h_lru')}")

    return _timed("fig2_heuristics", m.run, derive)


def fig3_static():
    from . import fig3_static as m

    def derive(rows):
        dtr = [r["overhead"] for r in rows
               if r["planner"] == "dtr_dtr" and r["ok"]]
        opt = [r["overhead"] for r in rows
               if r["planner"] == "revolve" and r["ok"]]
        a = sum(dtr) / max(len(dtr), 1)
        b = sum(opt) / max(len(opt), 1)
        return f"mean_overhead dtr={a:.3f} revolve_optimal={b:.3f}"

    return _timed("fig3_static", m.run, derive)


def fig4_overhead():
    from . import fig4_overhead as m

    def derive(rows):
        acc = {}
        for r in rows:
            if r["bench"] == "meta" and r["ok"]:
                acc.setdefault(r["heuristic"], []).append(
                    r["meta_accesses"])
        parts = [f"{h}={int(sum(v)/len(v))}" for h, v in sorted(acc.items())]
        return "mean_meta_accesses " + " ".join(parts)

    return _timed("fig4_overhead",
                  lambda: m.run_meta_accesses() + m.run_planner_wallclock(),
                  derive)


def fig5_theorem():
    from . import fig5_theorem as m

    def derive(rows):
        t31 = [r for r in rows if r["bench"] == "thm31"]
        first, last = t31[0]["ops_per_n"], t31[-1]["ops_per_n"]
        return f"thm31 ops/N {first}->{last} (flat=O(N) confirmed)"

    return _timed("fig5_theorem", lambda: m.run_thm31() + m.run_thm32(),
                  derive)


def table1_maxinput():
    from . import table1_maxinput as m

    def derive(rows):
        gains = [r["gain"] for r in rows]
        return f"mean_input_gain={sum(gains)/len(gains):.2f}x"

    return _timed("table1_maxinput",
                  lambda: m.run_simulated() + m.run_eager_treelstm(), derive)


def fig_fragmentation():
    from . import fig_fragmentation as m

    def derive(rows):
        gaps = [e["budget_gap"] for e in rows if e["budget_gap"] is not None]
        mean = sum(gaps) / max(len(gaps), 1)
        return f"models={len(rows)} mean_counter_vs_pool_gap={mean:.3f}"

    return _timed("fig_fragmentation",
                  lambda: list(m.run()["models"].values()), derive)


def perf_runtime():
    from . import perf_runtime as m

    def derive(rows):
        head = rows[0]["headline"]
        if rows[0]["equivalence_failures"]:
            return f"EQUIVALENCE FAILURES={rows[0]['equivalence_failures']}"
        parts = [f"{h}={v['pick_speedup']}x" for h, v in sorted(head.items())]
        return (f"pick_speedup[{rows[0]['headline_chain']}] "
                + " ".join(parts))

    return _timed("perf_runtime", lambda: [m.run(smoke=True)], derive)


def serving():
    """Captured serving/train traces -> budget curves (BENCH_serving.json)."""
    import json

    def fn():
        from repro.trace.__main__ import main as trace_main
        code = trace_main([
            "report", "--smoke", "--heuristics", "h_dtr_eq", "h_lru",
            "--fractions", "0.9", "0.7", "0.5", "0.3",
            "--thrash-factor", "10", "--out", "BENCH_serving.json"])
        with open("BENCH_serving.json") as f:
            rep = json.load(f)
        rep["exit"] = code
        return [rep]

    def derive(rows):
        rep = rows[0]
        if rep["equivalence_failures"]:
            return f"EQUIVALENCE FAILURES={rep['equivalence_failures']}"
        serve = [c["min_feasible_fraction"] for c in rep["curves"]
                 if c["trace"].startswith("serve")
                 and c["heuristic"] == "h_dtr_eq"]
        return (f"traces={len(rep['traces'])} oracle-equivalent; "
                f"serve min_budget(h_dtr_eq)="
                f"{[round(x, 2) if x else None for x in serve]}")

    return _timed("serving", fn, derive)


def perf_offload():
    from . import perf_offload as m

    def derive(rows):
        rep = rows[0]
        if not rep["gate"]["ok"] or not rep["equivalence"]["ok"]:
            return "OFFLOAD GATE FAILED"
        return (f"cells={len(rep['rows'])} "
                f"hybrid_wins={len(rep['hybrid_wins'])}")

    return _timed("perf_offload", lambda: [m.run(smoke=True)], derive)


def perf_static():
    from . import perf_static as m

    def derive(rows):
        rep = rows[0]
        if not rep["ok"]:
            return f"STATIC INVARIANTS FAILED({len(rep['violations'])})"
        gaps = [cell["dtr"]["h_dtr"]["gap_vs_static"]
                for c in rep["curves"] for cell in c["cells"]
                if cell["dtr"].get("h_dtr", {}).get("gap_vs_static")]
        mean = sum(gaps) / max(len(gaps), 1)
        n_feas = sum(1 for c in rep["curves"] for cell in c["cells"]
                     if cell["static"] is not None)
        return (f"feasible_cells={n_feas} "
                f"mean_dtr_vs_static_gap={mean:.3f}")

    return _timed("perf_static", lambda: [m.run(smoke=True)], derive)


def perf_faults():
    from . import perf_faults as m

    def derive(rows):
        rep = rows[0]
        if not rep["gates"]["ok"]:
            return "FAULTS GATE FAILED"
        surv = min((s["survival"] for s in rep["survival"]), default=1.0)
        return (f"cells={len(rep['rows'])} min_survival={surv} "
                f"degradations="
                f"{sum(s['degradations'] for s in rep['survival'])}")

    return _timed("perf_faults", lambda: [m.run(smoke=True)], derive)


def main() -> None:
    print("name,us_per_call,derived")
    fig2_heuristics()
    fig3_static()
    fig4_overhead()
    fig5_theorem()
    table1_maxinput()
    fig_fragmentation()
    perf_runtime()
    serving()
    perf_offload()
    perf_static()
    perf_faults()


if __name__ == "__main__":
    main()
