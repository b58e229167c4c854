"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing jax;
everything else (tests, benches, training) sees the real local devices and
builds small meshes via :mod:`repro.dist.mesh`. Axes are ``Auto``, as there:
``Explicit`` axes (``jax.make_mesh``'s default) refuse the model's
``with_sharding_constraint`` hints.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading pod axis (2 pods).

    Axis semantics: ``pod`` = cross-pod DP over DCN; ``data`` = in-pod DP +
    FSDP; ``model`` = TP/EP over ICI.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — run "
            "under launch/dryrun.py (it forces 512 host devices) or on a pod")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:need])
