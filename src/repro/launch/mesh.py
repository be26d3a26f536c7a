"""Host mesh construction.

``make_host_mesh`` is a function, not a module-level constant, so that
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_host_mesh(n_devices: int = 0) -> Mesh:
    """Data-parallel mesh over this host's devices: all of them, or the
    first ``n_devices`` (a one-device reference run on a multi-chip host).
    The ``"model"`` axis has size 1."""
    devices = jax.devices()
    if n_devices:
        if n_devices > len(devices):
            raise ValueError(f"asked for {n_devices} devices, have "
                             f"{len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices).reshape(len(devices), 1),
                ("data", "model"))
