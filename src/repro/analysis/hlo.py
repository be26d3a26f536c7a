"""XLA's own cost analysis as a plain dict.

``compiled.cost_analysis()`` counts each computation once; the loop-aware
counts (a scanned layer body times its trip count, collective bytes by
kind) are in ``hlo_cost``.
"""
from __future__ import annotations


def xla_cost_dict(cost_analysis) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty where the backend has
    no cost model and returns None)."""
    return dict(cost_analysis or {})
