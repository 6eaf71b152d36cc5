"""Device-mesh construction (SURVEY.md §5 distributed backend).

The north-star topology is a v5e-16 — a single ICI domain — so the default
mesh is 1-D ``('data',)``. A second ('dcn') axis for multi-slice scaling
composes with the same step body: grads are pmean-ed over both axes and XLA
routes each reduction over the right fabric.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh


def device_count() -> int:
    return len(jax.devices())


def make_mesh(
    n_devices: int | None = None, axis: str = "data", devices=None
) -> Mesh:
    """1-D data mesh over the first ``n_devices`` visible devices."""
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devs)} visible"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))

