"""Data pipeline: deterministic synthetic token streams, shard-aware."""
from .pipeline import SyntheticLM

__all__ = ["SyntheticLM"]
