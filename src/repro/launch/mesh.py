"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state.  The single-pod mesh is
16x16 = 256 chips (one v5e pod); the multi-pod mesh is 2x16x16 = 512 chips
with a leading "pod" axis that composes with "data" for batch/gradient
sharding (only reduce-scatter traffic crosses the pod boundary — DCN/ICI
friendly).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            "dry-run launcher must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    if len(devices) > n:
        devices = devices[:n]
    import numpy as np
    return Mesh(np.asarray(devices).reshape(shape), axes)


def make_host_mesh(model: int = 1, devices=None) -> Mesh:
    """Mesh over local devices (tests / examples / local fleet): every
    device of the process, or exactly ``devices`` when given (a one-chip
    fleet on a four-chip host passes ``jax.devices()[:1]``).

    ``model > 1`` carves a model-parallel axis out of those devices so
    fleet members build their params and decode state sharded under
    ``sharding/rules.py`` (large-member sharding; force extra host devices
    with XLA_FLAGS=--xla_force_host_platform_device_count=N to exercise it
    on CPU)."""
    import numpy as np
    devices = list(jax.devices() if devices is None else devices)
    if model < 1 or len(devices) < model:
        raise RuntimeError(
            f"model axis {model} needs at least {model} devices, have "
            f"{len(devices)} — set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=N before importing jax to emulate more hosts")
    data = len(devices) // model
    return Mesh(np.asarray(devices[: data * model]).reshape(data, model),
                ("data", "model"))


def data_axes(mesh: Mesh):
    """Axes that carry batch/gradient sharding."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def stage_split(mesh: Mesh, n_stages: int):
    """Pipeline-parallel hook: partition the 'model' axis into stages.

    The baseline meshes are DP x TP; this helper documents/enables a future
    circular-schedule PP launcher (see DESIGN.md §5) by returning the device
    slices a stage scheduler would own.  Not used by the baseline paths.
    """
    axis = mesh.axis_names.index("model")
    size = mesh.devices.shape[axis]
    assert size % n_stages == 0
    per = size // n_stages
    import numpy as np
    return [np.take(mesh.devices, range(s * per, (s + 1) * per), axis=axis)
            for s in range(n_stages)]
