"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state. The dry-run forces 512 host
devices via XLA_FLAGS before any jax import (see dryrun.py lines 1-2).

Single pod : (16, 16)      axes (data, model)   — 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   axes (pod, data, model) — 512 chips; the 'pod'
             axis is pure data parallelism (gradient all-reduce crosses DCI).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh over forced host devices (tests)."""
    return _auto_mesh(shape, axes)


def required_devices(*, multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
