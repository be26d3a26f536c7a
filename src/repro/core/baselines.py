"""Static checkpointing baselines for the Fig. 3 comparison.

All baselines plan offline for a *linear chain* of N unit ops (the setting
where optimal static planning is tractable without an ILP solver; no ILP
solver is a dependency of this package):

  * ``chen_sqrt``   — Chen et al. (2016) √N segmentation: recompute each
                      segment once during backward.
  * ``chen_greedy`` — Chen's greedy variant: checkpoints every ``b`` bytes.
  * ``revolve``     — Griewank & Walther binomial checkpointing (optimal for
                      the one-shot adjoint regime, O(N log N) ops at
                      O(log N) memory).
  * ``optimal_dp``  — exact DP over (chain length, checkpoint slots), the
                      Checkmate-equivalent optimum for chains.

Each returns total forward-op executions (the backward ops themselves are the
same N for every planner, so comparisons report *extra recomputation*).
"""
from __future__ import annotations

import math
from functools import lru_cache


def chen_sqrt(n: int) -> tuple[int, int]:
    """(total_fwd_ops, peak_memory_tensors) for √N segmentation.

    Forward pass stores one checkpoint every k=⌈√N⌉ ops; backward
    recomputes each segment once from its checkpoint.
    """
    k = max(int(math.ceil(math.sqrt(n))), 1)
    n_ckpt = (n + k - 1) // k
    # Forward: n ops.  Backward: each segment replayed once (≤ k-1 ops each).
    recompute = sum(max(min(k, n - i * k) - 1, 0) for i in range(n_ckpt))
    peak = n_ckpt + k + 2  # checkpoints + live segment + grad pair
    return n + recompute, peak


def chen_greedy(n: int, budget: int) -> tuple[int, int]:
    """Greedy: place a checkpoint every ⌈n/(budget-2)⌉ ops to fit budget."""
    slots = max(budget - 2, 1)
    k = max((n + slots - 1) // slots, 1)
    n_ckpt = (n + k - 1) // k
    recompute = sum(max(min(k, n - i * k) - 1, 0) for i in range(n_ckpt))
    return n + recompute, n_ckpt + k + 2


@lru_cache(maxsize=None)
def _revolve_cost(n: int, s: int) -> int:
    """Minimal forward re-evaluations to reverse a chain of length n with s
    checkpoint slots (Griewank's binomial schedule, computed by DP)."""
    if n <= 1:
        return 0
    if s <= 0:
        return math.inf  # cannot reverse without any checkpoint
    if s == 1:
        # Recompute from the start for every step: n-1 + n-2 + ... + 1
        return n * (n - 1) // 2
    best = math.inf
    for k in range(1, n):
        # Place a checkpoint after k ops: k fwd ops to reach it, then reverse
        # the tail with s-1 slots, then the head with s slots.
        c = k + _revolve_cost(n - k, s - 1) + _revolve_cost(k, s)
        if c < best:
            best = c
    return best


def revolve(n: int, budget: int) -> tuple[int, int]:
    """(total_fwd_ops, peak) for binomial checkpointing with ``budget`` slots."""
    s = max(budget - 2, 1)
    extra = _revolve_cost(n, s)
    return n + int(extra), budget


def optimal_dp(n: int, budget: int) -> tuple[int, int]:
    """Exact optimum for a unit chain = REVOLVE's DP (provably optimal for
    the one-shot reversal of a homogeneous chain)."""
    return revolve(n, budget)


BASELINES = {
    "chen_sqrt": lambda n, b: chen_sqrt(n),
    "chen_greedy": chen_greedy,
    "revolve": revolve,
    "optimal_dp": optimal_dp,
}


def chain_solvers():
    """Bridge to the trace-level planners of ``repro.static.solvers``.

    The closed-form baselines above plan on *homogeneous unit chains*
    (every op costs 1, every tensor weighs 1 slot).  The ``repro.static``
    solvers generalize them to heterogeneous chains extracted from real
    traces (per-item byte sizes, per-segment recompute costs) and return
    executable ``Plan``s rather than op counts.  Returns the ``{name:
    solver(chain, budget) -> Plan}`` registry; imported lazily so the
    core package stays dependency-free of the static subsystem.
    """
    from ..static.solvers import SOLVERS
    return dict(SOLVERS)
