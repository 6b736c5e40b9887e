"""Mesh construction. Functions only — importing this module never touches
jax device state (device count is locked at first jax init)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes. JAX 0.9 defaults to
    ``Explicit`` axes, which the model code (GSPMD-style, sharded by
    ``with_sharding_constraint`` and jit in/out shardings) is not written
    for: the first embedding gather fails with ``ShardingTypeError``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The production mesh: 16x16 (data, model) per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_dryrun_mesh(*, multi_pod: bool = False) -> Mesh:
    """Production mesh when 512 devices exist; proportionally scaled-down
    mesh for debug runs with fewer placeholder devices."""
    n = len(jax.devices())
    if n >= 512 or (not multi_pod and n >= 256):
        return make_production_mesh(multi_pod=multi_pod)
    if multi_pod:
        per_pod = n // 2
        model = max(1, int(per_pod ** 0.5))
        while per_pod % model:
            model -= 1
        return _auto_mesh((2, per_pod // model, model),
                          ("pod", "data", "model"))
    model = max(1, int(n ** 0.5))
    while n % model:
        model -= 1
    return _auto_mesh((n // model, model), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1,
                   pod: Optional[int] = None) -> Mesh:
    """Small mesh over however many (host) devices exist — for tests."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def describe(mesh: Mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items())
