"""Optimizers (built from scratch — no optax offline): AdamW, Adafactor, SGD-m.

All optimizers are pure pytree transforms with ZeRO-1-friendly state layout:
the state tree mirrors the param tree, so sharding rules (params sharded over
``model``, optionally ``fsdp`` over ``data``) apply to the state unchanged —
which is exactly ZeRO when FSDP is on.

deepseek-v3-671b trains with Adafactor (factored second moment, no first
moment): 671B f32 params with f32 gradients and AdamW's two f32 moments
(16 bytes per param, 10.7 TB) cannot fit a 512-chip v5e slice (8.8 TB of
HBM); Adafactor + bf16 params does.
"""
from .optimizers import (
    OptState, adafactor, adamw, apply_updates, clip_by_global_norm,
    make_optimizer, sgdm, cosine_schedule,
)

__all__ = [
    "OptState", "adafactor", "adamw", "apply_updates",
    "clip_by_global_norm", "make_optimizer", "sgdm", "cosine_schedule",
]
