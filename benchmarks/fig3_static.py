"""Fig. 3 reproduction: DTR vs static checkpointing planners.

On linear chains (where optimal static planning is tractable in closed form /
DP — Checkmate's ILP solver is not a dependency of this package),
compares total executed forward ops:

  dtr_*        — online, no advance knowledge (h_dtr, h_dtr_eq, h_lru)
  chen_sqrt    — Chen et al. √N segmentation (budget-oblivious)
  chen_greedy  — Chen greedy at the same budget
  revolve      — Griewank binomial schedule (optimal one-shot reversal)

Overhead ratio = total_ops / (2N) (the unconstrained fwd+bwd op count).
"""
from __future__ import annotations

import math
import time

from repro.core import baselines, graphs
from repro.core.graph import replay
from repro.core.heuristics import by_name
from repro.core.runtime import DTRRuntime, OOMError, ThrashError


def run(ns=(64, 128, 256, 512), budget_fracs=(0.5, 0.25, 0.125)):
    rows = []
    for n in ns:
        for bf in budget_fracs:
            budget = max(int(n * bf), 6)
            # --- DTR variants (budget counts tensors; unit sizes) ---
            for h in ("h_dtr", "h_dtr_eq", "h_lru"):
                log = graphs.linear_network(n)
                rt = DTRRuntime(budget=budget, heuristic=by_name(h),
                                compute_limit=500.0 * n)
                t0 = time.perf_counter()
                try:
                    replay(log, rt)
                    ops = rt.ops_executed
                    ok = True
                except (OOMError, ThrashError):
                    ops, ok = 0, False
                wall = time.perf_counter() - t0
                rows.append(dict(
                    planner=f"dtr_{h[2:]}", n=n, budget=budget, ok=ok,
                    total_ops=ops,
                    overhead=round(ops / (2 * n), 3) if ok else "",
                    plan_us=int(wall * 1e6)))
            # --- static planners (forward ops + N backward ops) ---
            for name in ("chen_sqrt", "chen_greedy", "revolve"):
                t0 = time.perf_counter()
                fwd_ops, peak = baselines.BASELINES[name](n, budget)
                wall = time.perf_counter() - t0
                total = fwd_ops + n
                feasible = peak <= budget
                rows.append(dict(
                    planner=name, n=n, budget=budget, ok=feasible,
                    total_ops=total, overhead=round(total / (2 * n), 3),
                    plan_us=int(wall * 1e6)))
    return rows


def main(argv=()):
    rows = run()
    print("planner,n,budget,ok,total_ops,overhead,plan_us")
    for r in rows:
        print(",".join(str(r[k]) for k in
                       ("planner", "n", "budget", "ok", "total_ops",
                        "overhead", "plan_us")))
    return rows


if __name__ == "__main__":
    main()
