"""Production mesh construction (TPU v5e target).

Single-pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model).

A FUNCTION (not a module constant) so importing this module never touches
jax device state — smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_devices: int = 8):
    """Small mesh over however many (fake) devices tests set up."""
    return jax.make_mesh((n_devices // 2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)
