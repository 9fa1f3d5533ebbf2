"""Production mesh construction.

Single pod: 16 x 16 = 256 chips (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips (pod, data, model); the 'pod' axis
carries pure data parallelism (and is the gradient-compression hop).

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host has (smoke tests / examples): 1xN mesh."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
