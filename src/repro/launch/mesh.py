"""Production mesh construction (spec'd in the assignment).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax

from repro.configs.base import MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_from_config(mesh_cfg: MeshConfig):
    return make_production_mesh(multi_pod=mesh_cfg.multi_pod)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) devices exist — used by
    smoke-scale distributed tests."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
