"""Mesh construction helpers (the worker-pool analog of
``src/engine/dataflow/config.rs`` — PATHWAY_THREADS/PROCESSES become mesh
axes)."""

from __future__ import annotations

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(axes: dict[str, int] | None = None) -> Mesh:
    """Mesh over all available devices with the given axis sizes."""
    devices = jax.devices()
    if axes is None:
        axes = {"data": len(devices)}
    sizes = list(axes.values())
    if int(np.prod(sizes)) != len(devices):
        raise ValueError(f"axes {axes} do not cover {len(devices)} devices")
    dev_array = np.array(devices).reshape(sizes)
    return Mesh(dev_array, tuple(axes.keys()))


def data_model_mesh(n_devices: int | None = None) -> Mesh:
    """2D (data, model) mesh: model axis 2 when the device count allows,
    else pure data parallel. The default layout for embedder TP + index DP."""
    devices = jax.devices()
    n = n_devices if n_devices is not None else len(devices)
    devices = devices[:n]
    model = 2 if n % 2 == 0 and n >= 2 else 1
    data = n // model
    dev_array = np.array(devices).reshape(data, model)
    return Mesh(dev_array, ("data", "model"))
