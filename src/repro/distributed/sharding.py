"""Logical-axis sharding rules (MaxText-style) for a device mesh.

Model code annotates tensors with *logical* axes (``shard(x, "batch", None,
"embed")``); a single rules table maps logical axes to physical mesh axes.
Flipping parallelism strategy (pure DP, TP, FSDP, SP, EP) touches only this
table / per-run overrides — never model code.

Physical mesh axes: ``("data", "model")`` (launch/mesh.make_host_mesh).
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical axis -> physical mesh axis (or tuple of axes, or None=replicated).
LOGICAL_RULES: dict[str, object] = {
    "batch": "data",            # data parallel
    "seq": None,                # sequence replicated by default (SP flips this)
    "seq_model": "model",       # explicit sequence-parallel annotation
    "embed": None,              # activation d_model dim replicated
    "heads": "model",           # TP over attention heads
    "kv_heads": "model",
    "mlp": "model",             # TP over FFN hidden
    "vocab": "model",           # TP over vocab (embedding + logits)
    "expert": "model",          # EP: experts over model axis
    "expert_cap": "data",       # expert capacity dim over data
    "kv_seq": None,             # KV-cache sequence dim
    "fsdp": "data",             # param dim additionally sharded when FSDP on
    "lru": "model",             # RG-LRU width
    "conv": None,
}


class _State(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.overrides: dict[str, object] = {}
        self.fsdp: bool = False


_STATE = _State()


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh], overrides: dict | None = None,
                 fsdp: bool = False):
    """Activate a mesh + rule overrides for model-code sharding constraints."""
    prev = (_STATE.mesh, _STATE.overrides, _STATE.fsdp)
    _STATE.mesh = mesh
    _STATE.overrides = dict(overrides or {})
    _STATE.fsdp = fsdp
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _STATE.mesh, _STATE.overrides, _STATE.fsdp = prev


def current_mesh() -> Optional[Mesh]:
    return _STATE.mesh


def fsdp_enabled() -> bool:
    return _STATE.fsdp


def _resolve(axis: Optional[str], mesh: Mesh) -> object:
    if axis is None:
        return None
    rules = {**LOGICAL_RULES, **_STATE.overrides}
    phys = rules.get(axis, None)
    if phys is None:
        return None
    if isinstance(phys, (tuple, list)):
        present = tuple(a for a in phys if a in mesh.axis_names)
        return present if present else None
    return phys if phys in mesh.axis_names else None


def _fit(r, dim: Optional[int], mesh: Mesh):
    """Keep only a prefix of mesh axes whose product divides ``dim``.

    GQA head counts (3, 2, 1…) and tiny batches don't divide a 16-way axis;
    we degrade to replication (or partial sharding for tuple axes) instead
    of failing — the divisibility rule GSPMD enforces on explicit shardings.
    """
    if r is None or dim is None:
        return r
    axes = r if isinstance(r, tuple) else (r,)
    kept = []
    prod = 1
    for a in axes:
        prod *= mesh.shape[a]
        if dim % prod == 0:
            kept.append(a)
        else:
            break
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def pspec(*axes: Optional[str], mesh: Optional[Mesh] = None,
          shape: Optional[tuple] = None) -> P:
    """PartitionSpec from logical axes under the active rules.

    With ``shape``, axes that don't divide the dimension are dropped
    (prefix-reduced for tuple mappings)."""
    mesh = mesh or _STATE.mesh
    if mesh is None:
        return P()
    resolved, used = [], set()
    for i, ax in enumerate(axes):
        r = _resolve(ax, mesh)
        if shape is not None:
            r = _fit(r, shape[i] if i < len(shape) else None, mesh)
        # Never map two tensor dims to the same mesh axis.
        flat = r if isinstance(r, tuple) else (r,) if r else ()
        if any(f in used for f in flat):
            r = None
        else:
            used.update(flat)
        resolved.append(r)
    return P(*resolved)


def shard(x, *axes: Optional[str]):
    """Apply a logical sharding constraint (no-op without an active mesh)."""
    mesh = _STATE.mesh
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, pspec(*axes, mesh=mesh,
                                     shape=tuple(x.shape))))


# ---------------------------------------------------------------------------
# Parameter metadata
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParamInfo:
    """Shape/dtype/logical-axes record for one parameter tensor.

    ``fsdp_dim``: dimension index to shard additionally over the data axis
    when FSDP is enabled (ZeRO-3-style parameter sharding).
    """
    shape: tuple[int, ...]
    dtype: str = "float32"
    axes: tuple[Optional[str], ...] = ()
    fsdp_dim: Optional[int] = None
    init_scale: float = 0.02

    def __post_init__(self):
        assert len(self.axes) in (0, len(self.shape)), (self.shape, self.axes)


def param_pspec(info: ParamInfo, mesh: Optional[Mesh] = None,
                fsdp: Optional[bool] = None) -> P:
    mesh = mesh or _STATE.mesh
    if mesh is None:
        return P()
    fsdp = _STATE.fsdp if fsdp is None else fsdp
    axes = list(info.axes) if info.axes else [None] * len(info.shape)
    if fsdp and info.fsdp_dim is not None and axes[info.fsdp_dim] is None:
        axes[info.fsdp_dim] = "fsdp"
    return pspec(*axes, mesh=mesh, shape=tuple(info.shape))


def axis_resources(tree, mesh: Optional[Mesh] = None, fsdp: bool = False):
    """Map a pytree of ParamInfo to a pytree of NamedShardings."""
    mesh = mesh or _STATE.mesh

    def one(info: ParamInfo):
        return NamedSharding(mesh, param_pspec(info, mesh=mesh, fsdp=fsdp))

    return jax.tree.map(one, tree,
                        is_leaf=lambda x: isinstance(x, ParamInfo))


def shape_structs(tree):
    """ParamInfo tree -> ShapeDtypeStruct tree (lowering without
    allocation)."""
    def one(info: ParamInfo):
        return jax.ShapeDtypeStruct(info.shape, np.dtype(info.dtype))

    return jax.tree.map(one, tree,
                        is_leaf=lambda x: isinstance(x, ParamInfo))
