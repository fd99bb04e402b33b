"""Production meshes. Functions only — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run
sees 512 host-platform placeholders).

Mesh semantics (TPU v5e pod = 16x16 = 256 chips):
  * ``data``  — FSDP/ZeRO-3 parameter sharding + batch data parallelism.
  * ``model`` — tensor parallelism (TP) + expert parallelism (EP).
  * ``pod``   — pod-level data parallelism (gradient all-reduce crosses DCN);
    multi-pod meshes prepend it.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


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


# TPU v5e hardware constants for the roofline model.
HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link (~per chip per direction)
    "hbm_bytes": 16 * 1024**3,   # 16 GiB per chip
}
