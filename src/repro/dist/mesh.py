"""Meshes over the local devices (CPU tests, the examples, one host's chips).

Both build ``Auto`` axes: the model code places tensors with
``with_sharding_constraint`` hints, which ``Explicit`` axes
(``jax.make_mesh``'s default) refuse. The pod-scale meshes of the dry-run are
in :mod:`repro.launch.mesh`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_local_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first ``data * model`` local devices."""
    n = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:n])


def make_host_mesh():
    """(data, model) mesh over every local device: ``model`` takes the
    largest divisor of the device count not above its square root, so one
    chip gives 1×1 and a four-chip host 2×2."""
    n = len(jax.devices())
    model = max(m for m in range(1, int(n ** 0.5) + 1) if n % m == 0)
    return make_local_mesh(n // model, model)
