"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the
"pod" axis is the slow DCN dimension (data parallel across pods, gradient
all-reduce hierarchical, KV-handoff P→D crosses it in disaggregated
serving).

Functions, not module-level constants, so importing this module never
touches jax device state (device count is locked at first jax init —
dryrun.py must set XLA_FLAGS before any import).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (hillclimb variants: e.g. (8, 32), (4, 64)). Axes
    are Auto: the model's ``with_sharding_constraint`` anchors and the
    launch layer's ``NamedSharding`` rules are written for Auto axes, and
    ``jax.make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> Tuple[str, ...]:
    """The batch-sharding axes of a mesh — ('pod','data') when multi-pod."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def device_count(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
