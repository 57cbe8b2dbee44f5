"""Production mesh builders.

make_production_mesh is a FUNCTION (importing this module never touches jax
device state). Axis layout follows the "no-NAT" rule (DESIGN.md §2): "model"
(TP) and "data" (FSDP) ride intra-pod ICI; only "pod" crosses DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto: the model code places arrays
    with sharding constraints and lets the partitioner propagate, which
    the Explicit axes that make_mesh defaults to do not allow."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over the available devices (subprocess tests)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    model = max(1, min(model, n))
    while n % model:
        model -= 1
    return make_auto_mesh((n // model, model), ("data", "model"),
                          devices=devs[:n])
