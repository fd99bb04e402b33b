"""Meshes. Functions only — importing this module never touches jax
device state.

Mesh semantics:
  * ``data``  — FSDP/ZeRO-3 parameter sharding + batch data parallelism.
  * ``model`` — tensor parallelism (TP) + expert parallelism (EP).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / local runs).

    ``data`` must divide the device count exactly: the old ``n // data``
    truncation silently dropped devices and could hand back a smaller mesh
    than requested — a mesh bug that surfaces much later as wrong collective
    sizes. ``model`` is still clamped (it is a per-host convenience knob),
    but never below 1 and never beyond what the remaining devices allow.
    """
    n = len(jax.devices())
    if data < 1 or n % data != 0:
        raise ValueError(
            f"make_host_mesh: data={data} must be a positive divisor of the "
            f"device count ({n} device{'s' if n != 1 else ''} available); "
            f"got remainder {n % data if data >= 1 else data}")
    model = max(1, min(model, n // data))
    devs = np.array(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))

