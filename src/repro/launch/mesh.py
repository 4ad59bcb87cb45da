"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod axis
carries pure data parallelism across the inter-pod (DCN) boundary.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (elastic re-mesh / tests).  Axes are Auto: the
    model's `with_sharding_constraint` hooks need Auto axes, and
    `jax.make_mesh` defaults to Explicit ones."""
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_host_test_mesh(n_devices: int = 8, model: int = 2):
    """Small mesh over host platform devices for CPU integration tests."""
    devs = jax.devices()[:n_devices]
    data = len(devs) // model
    arr = np.array(devs[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(arr, ("data", "model"))
