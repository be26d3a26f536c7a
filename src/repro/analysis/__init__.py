"""Compiled-artifact analysis: loop-aware HLO cost and XLA's cost dict."""
from .hlo import xla_cost_dict

__all__ = ["xla_cost_dict"]
