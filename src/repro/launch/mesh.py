"""Mesh construction for the production pod(s).

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "compat_make_mesh",
    "make_production_mesh",
    "make_test_mesh",
    "device_count_needed",
]


def compat_make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips/pod; multi-pod adds a leading pod=2 axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int | None = None) -> Mesh:
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod:
        return compat_make_mesh((pod, data, model), ("pod", "data", "model"))
    return compat_make_mesh((data, model), ("data", "model"))


def device_count_needed(multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
