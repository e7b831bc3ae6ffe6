"""Mesh construction for the production topology.

TPU v5e: 16x16 = 256 chips per pod; multi-pod adds a leading "pod" axis
across the DCN boundary (2 pods = 512 chips).  Functions, not module-level
constants, so importing never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_tile_mesh(n: int, m: int, axes=("th", "tw")) -> Mesh:
    """Paper-native 2-D tile grid (YOLO benchmarks / exactness tests)."""
    return _make((n, m), axes)


def make_local_mesh(axes=("data", "model")) -> Mesh:
    """Whatever devices exist locally, as a (1, ndev) mesh (smoke tests)."""
    n = len(jax.devices())
    return _make((1, n), axes)
