"""Production mesh construction (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module never
touches JAX device state (the dry-run must set XLA_FLAGS before first
device query).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagation
    decides placement, as the partition specs in ``sharding/plan.py``
    expect)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = 1, min(model, n)
    return make_mesh((data, model), ("data", "model"))
