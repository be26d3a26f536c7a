"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run sets XLA_FLAGS before any jax initialization.

Single pod:  (16, 16)      axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    assert len(devices) >= n, (
        f"need {n} devices for mesh {shape}, have {len(devices)} — the "
        f"dry-run must set --xla_force_host_platform_device_count")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh(model_axis: int = 1, n_devices: int = 0) -> Mesh:
    """Mesh over this host's devices: all of them, or the first
    ``n_devices`` (a one-device reference run on a multi-chip host)."""
    devices = jax.devices()
    if n_devices:
        if n_devices > len(devices):
            raise ValueError(f"asked for {n_devices} devices, have "
                             f"{len(devices)}")
        devices = devices[:n_devices]
    n = len(devices)
    model_axis = min(model_axis, n)
    data_axis = n // model_axis
    return Mesh(
        np.asarray(devices[: data_axis * model_axis]).reshape(
            data_axis, model_axis),
        ("data", "model"))
