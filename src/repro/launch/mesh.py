"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis maps to
DCN, "data"/"model" to ICI — the AVEC link-hierarchy rule keeps TP collectives
on ICI and only (optionally compressed) gradient reductions on DCN.

Defined as functions so importing this module never touches jax device state
(jax locks the device count on first backend init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh for CPU smoke paths (tests/benchmarks)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
