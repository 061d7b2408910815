"""Production mesh factory.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis is
pure data parallelism over the inter-pod DCI links (gradient all-reduce
is hierarchically scheduled — see repro.distributed and DESIGN.md §5).

A FUNCTION, not a module constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py
forces 512 host devices via XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_devices: int = 8):
    """Small mesh for CPU sharding tests (n must divide available devices)."""
    return jax.make_mesh(
        (n_devices // 4, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
