"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
is the outer data-parallel dimension (gradient all-reduce crosses pods over
DCN; everything else stays inside a pod's ICI).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devices)} are "
            f"visible — the dry-run entrypoint must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} before "
            f"the first device query")
    return jax.make_mesh(
        shape, axes, devices=devices[:need],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: Optional[int] = None,
                   devices: Optional[Sequence] = None):
    """(data, model) mesh over ``devices`` (default: every visible device)."""
    devices = list(jax.devices() if devices is None else devices)
    model = model or 1
    data = len(devices) // model
    return jax.make_mesh(
        (data, model), ("data", "model"), devices=devices[: data * model],
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
