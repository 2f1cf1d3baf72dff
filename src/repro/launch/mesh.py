"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any
device initialization).

Single pod:  (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips
The "pod" axis is an outer data-parallel axis crossing the DCN; "data" is
in-pod DP; "model" is the TP/EP/sequence-flash-decode axis on ICI.
"""

from __future__ import annotations

from repro.core.executor import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_size(mesh) -> int:
    return mesh.shape.get("model", 1)
