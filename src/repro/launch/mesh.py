"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state. The single-pod mesh
is 16 x 16 = 256 chips (v5e pod); multi-pod adds a leading ``pod`` axis
(2 pods = 512 chips). The ``pod`` axis extends the *vertical* layer of the
paper: work sharded along it (vector bundles, data-parallel replicas)
never communicates during SpMV / forward-backward — only gradient
reduction and redistribution cross it.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)}; "
            "run under src/repro/launch/dryrun.py which sets "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
